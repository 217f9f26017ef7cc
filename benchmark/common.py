"""Small helpers every process of the benchmark shares. Nothing here imports
jax or the program."""

from __future__ import annotations

import importlib.util
import json
import os
import urllib.request


def write_json(path: str, obj) -> None:
    """Write whole or not at all: a reader polling for `path` never sees half."""
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """The Python file at `path`, as a module of its own."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toml_text(config: dict) -> str:
    """The TOML the CLI server runs: the sections under the configuration's
    `toml` key, written out as they stand. A configuration carries `[server]
    model_kind, num_fields, buckets` and `[model]`, which leaves every other
    option at the program's default; a four-chip one adds its `[mesh]`."""

    def value(v) -> str:
        if isinstance(v, bool):
            return "true" if v else "false"
        if isinstance(v, (int, float)):
            return repr(v)
        if isinstance(v, str):
            return json.dumps(v)
        if isinstance(v, list):
            return "[" + ", ".join(value(x) for x in v) + "]"
        raise ValueError(f"cannot write {v!r} as TOML")

    lines = []
    for name, body in config["toml"].items():
        lines.append(f"[{name}]")
        lines += [f"{k} = {value(v)}" for k, v in body.items()]
        lines.append("")
    return "\n".join(lines)


def monitoring(rest_port: int, section: str):
    """One block of the server's `/monitoring`, its public counters."""
    url = f"http://127.0.0.1:{rest_port}/monitoring?section={section}"
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.load(r)[section]
