"""The smokes under tools/ that cost seconds, run as the programs they are:
each starts the server the CLI starts (`create_server`) in a process of its
own, prints one JSON line and exits 0 only when every judgement held."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("script", ["check_streaming_smoke.py", "check_mesh_smoke.py"])
def test_tool_smoke_exits_clean(script, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # conftest's 8 devices: a smoke asks for its own
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / script)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["errors"] == [], line["errors"]
