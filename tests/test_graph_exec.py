"""GraphDef-executor tests (C13: arbitrary-export execution).

Real `tf.saved_model.save` exports are built in TensorFlow subprocesses (TF
must never be imported in this process — its generated protos collide with
the vendored bindings in the descriptor pool), then served natively by
interop/graph_exec.py: eager parity vs TF's own forward, the full
gRPC-serving path with int64 ids past 2^31 (the x64 jit path), the
zoo -> generic -> graph fallback chain, and the documented unsupported-op
boundary.
"""

import asyncio
import json
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

enable_x64 = jax.enable_x64

from distributed_tf_serving_tpu.client import ShardedPredictClient
from distributed_tf_serving_tpu.interop.graph_exec import (
    GraphExecutor,
    UnsupportedOpError,
    graph_model,
)
from distributed_tf_serving_tpu.interop.savedmodel import (
    import_savedmodel,
    read_saved_model,
    serve_meta_graph,
)
from distributed_tf_serving_tpu.models import ModelConfig, ServableRegistry
from distributed_tf_serving_tpu.serving import DynamicBatcher, PredictionServiceImpl
from distributed_tf_serving_tpu.serving.server import create_server

F = 6  # fields

# An architecture deliberately OUTSIDE the zoo and the generic embed+MLP
# fallback: field-attention pooling (softmax over a learned field score),
# an einsum bilinear term, a residual tanh block, and a clipped output.
_EXPORT_EXOTIC = f"""
import sys
import numpy as np
import tensorflow as tf

out = sys.argv[1]
F = {F}
D = 8
rng = np.random.RandomState(11)


class Exotic(tf.Module):
    def __init__(self):
        super().__init__()
        self.emb = tf.Variable(rng.randn(997, D).astype(np.float32), name="emb")
        self.attn = tf.Variable(rng.randn(D, 1).astype(np.float32), name="attn")
        self.bilinear = tf.Variable(rng.randn(D, D).astype(np.float32) / 8.0, name="bilinear")
        self.w1 = tf.Variable(rng.randn(D, D).astype(np.float32) / 4.0, name="w1")
        self.b1 = tf.Variable(np.zeros(D, np.float32), name="b1")
        self.w2 = tf.Variable(rng.randn(2 * D, 1).astype(np.float32) / 4.0, name="w2")

    @tf.function(input_signature=[
        tf.TensorSpec([None, F], tf.int64, name="feat_ids"),
        tf.TensorSpec([None, F], tf.float32, name="feat_wts"),
    ])
    def __call__(self, feat_ids, feat_wts):
        e = tf.gather(self.emb, tf.math.floormod(feat_ids, 997))     # [n,F,D]
        e = e * feat_wts[..., None]
        scores = tf.squeeze(tf.einsum("nfd,dk->nfk", e, self.attn), -1)  # [n,F]
        alpha = tf.nn.softmax(scores, axis=-1)                       # [n,F]
        pooled = tf.reduce_sum(e * alpha[..., None], axis=1)         # [n,D]
        bil = tf.einsum("nd,de,ne->n", pooled, self.bilinear, pooled)
        h = tf.nn.tanh(tf.matmul(pooled, self.w1) + self.b1) + pooled
        feats = tf.concat([h, pooled], axis=-1)
        logit = tf.squeeze(tf.matmul(feats, self.w2), -1) + bil
        p = tf.clip_by_value(tf.sigmoid(logit), 1e-6, 1.0 - 1e-6)
        return {{"prediction_node": p}}


m = Exotic()
tf.saved_model.save(m, out, signatures={{"serving_default": m.__call__}})
"""

_GOLDEN = """
import sys, json
import numpy as np
import tensorflow as tf

src, seed, n, F = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
rng = np.random.RandomState(seed)
ids = rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64)
wts = rng.rand(n, F).astype(np.float32)
f = tf.saved_model.load(src).signatures["serving_default"]
out = f(feat_ids=tf.constant(ids), feat_wts=tf.constant(wts))
print(json.dumps([float(x) for x in out["prediction_node"].numpy()]))
"""


def _payload(n, seed):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(0, 1 << 40, size=(n, F)).astype(np.int64),
        "feat_wts": rng.rand(n, F).astype(np.float32),
    }


def _tf_golden(export_dir, seed, n):
    r = subprocess.run(
        [sys.executable, "-c", _GOLDEN, str(export_dir), str(seed), str(n), str(F)],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return np.asarray(json.loads(r.stdout.strip().splitlines()[-1]), np.float32)


@pytest.fixture(scope="module")
def exotic_export(tmp_path_factory):
    out = tmp_path_factory.mktemp("sm") / "exotic"
    r = subprocess.run(
        [sys.executable, "-c", _EXPORT_EXOTIC, str(out)],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        pytest.skip(f"tensorflow export unavailable: {r.stderr[-800:]}")
    return out


def test_graph_executor_matches_tf_forward(exotic_export):
    sv = import_savedmodel(
        exotic_export, "graph", ModelConfig(name="EX", num_fields=F), name="EX"
    )
    assert sv.model.needs_x64 and not sv.model.folds_ids_on_host
    arrays = _payload(12, seed=5)
    with enable_x64():
        out = sv.model.apply(sv.params, arrays)
    got = np.asarray(out["prediction_node"], np.float32)
    want = _tf_golden(exotic_export, seed=5, n=12)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_graph_servable_over_wire_preserves_int64(exotic_export):
    """Full stack: batcher pad (no fold), x64 jit, gRPC round trip. Ids are
    drawn past 2^31 so any silent int32 truncation would shift embedding
    rows and break parity with TF's forward."""
    sv = import_savedmodel(
        exotic_export, "graph", ModelConfig(name="EX", num_fields=F), name="EX"
    )
    registry = ServableRegistry()
    registry.load(sv)
    batcher = DynamicBatcher(buckets=(32, 64), max_wait_us=0).start()
    impl = PredictionServiceImpl(registry, batcher)
    server, port = create_server(impl, "127.0.0.1:0")
    server.start()
    try:
        arrays = _payload(10, seed=9)

        async def go():
            async with ShardedPredictClient([f"127.0.0.1:{port}"], "EX") as client:
                return await client.predict(arrays)

        got = asyncio.run(go())
        want = _tf_golden(exotic_export, seed=9, n=10)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    finally:
        server.stop(0)
        batcher.stop()


def test_fallback_chain_lands_on_graph_executor(exotic_export, caplog):
    """kind=dcn_v2 cannot bind the exotic export, the generic embed+MLP
    fallback cannot either; the importer must land on the graph executor
    (not an error) and serve correct scores."""
    import logging

    with caplog.at_level(logging.WARNING, logger="dts_tpu.interop"):
        sv = import_savedmodel(
            exotic_export, "dcn_v2",
            ModelConfig(name="EX", num_fields=F, vocab_size=997, embed_dim=8),
            name="EX",
        )
    assert not sv.model.folds_ids_on_host  # graph executor, not a zoo family
    arrays = _payload(6, seed=13)
    with enable_x64():
        got = np.asarray(sv.model.apply(sv.params, arrays)["prediction_node"], np.float32)
    np.testing.assert_allclose(got, _tf_golden(exotic_export, seed=13, n=6),
                               rtol=2e-5, atol=1e-6)
    assert any("GraphDef executor" in r.message for r in caplog.records)


def test_unsupported_op_is_named():
    """A graph using control flow must fail at import with the node name
    and op, per the documented executor boundary."""
    from distributed_tf_serving_tpu.proto import tf_meta_graph_pb2 as mg

    meta = mg.MetaGraphDef()
    sig = meta.signature_def["serving_default"]
    sig.inputs["x"].name = "x:0"
    sig.inputs["x"].dtype = 1
    sig.outputs["y"].name = "loop:0"
    sig.outputs["y"].dtype = 1
    n = meta.graph_def.node.add()
    n.name = "x"
    n.op = "Placeholder"
    n = meta.graph_def.node.add()
    n.name = "loop"
    n.op = "While"
    n.input.append("x")

    model, params = graph_model(meta, {}, name="bad")
    with pytest.raises(UnsupportedOpError, match="loop.*While|While.*loop"):
        model.apply(params, {"x": np.ones((2,), np.float32)})


def _tiny_meta(output_ref: str):
    """MetaGraphDef skeleton with one f32 input x:[None,4] and one output."""
    from distributed_tf_serving_tpu.proto import tf_meta_graph_pb2 as mg

    meta = mg.MetaGraphDef()
    sig = meta.signature_def["serving_default"]
    sig.inputs["x"].name = "x:0"
    sig.inputs["x"].dtype = 1
    sig.outputs["y"].name = output_ref
    sig.outputs["y"].dtype = 1
    n = meta.graph_def.node.add()
    n.name = "x"
    n.op = "Placeholder"
    return meta


def test_tf1_variable_v2_resolves_to_value():
    """TF1 ref-variables (VariableV2) yield the tensor value at every use
    site — there is no ReadVariableOp in a TF1 graph, so a VariableV2 ->
    Identity -> MatMul chain must see the array, not an opaque VarRef
    (round-3 advisor finding: this exact chain failed with a 0-d shape
    error while the docs claimed TF1 support)."""
    meta = _tiny_meta("mm:0")
    g = meta.graph_def
    v = g.node.add(); v.name = "w"; v.op = "VariableV2"
    ident = g.node.add(); ident.name = "w_read"; ident.op = "Identity"
    ident.input.append("w")
    mm = g.node.add(); mm.name = "mm"; mm.op = "MatMul"
    mm.input.extend(["x", "w_read"])

    rng = np.random.RandomState(0)
    w = rng.randn(4, 3).astype(np.float32)
    model, params = graph_model(meta, {"w": w}, name="tf1")
    x = rng.rand(5, 4).astype(np.float32)
    got = np.asarray(model.apply(params, {"x": x})["y"])
    np.testing.assert_allclose(got, x @ w, rtol=1e-6)


def test_tf1_variable_v2_missing_param_is_named():
    meta = _tiny_meta("w:0")
    v = meta.graph_def.node.add(); v.name = "w"; v.op = "VariableV2"
    model, params = graph_model(meta, {}, name="tf1")
    with pytest.raises(Exception, match="'w' not found"):
        model.apply(params, {"x": np.ones((1, 4), np.float32)})


def test_mod_is_truncated_remainder():
    """TF's Mod/TruncateMod are C-style (result takes the DIVIDEND's sign);
    FloorMod is Python-style. Both must hold on negative operands (round-3
    advisor finding: Mod was floor-mod, silently diverging)."""
    a = np.array([7, -7, 7, -7], np.int64)
    b = np.array([3, 3, -3, -3], np.int64)
    for op_name, want in (
        ("Mod", np.array([1, -1, 1, -1], np.int64)),        # C semantics
        ("TruncateMod", np.array([1, -1, 1, -1], np.int64)),
        ("FloorMod", np.array([1, 2, -2, -1], np.int64)),   # Python semantics
    ):
        from distributed_tf_serving_tpu.interop.graph_exec import _OPS

        (got,) = _OPS[op_name](None, [a, b], np)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=op_name)


_EXPORT_TF1 = """
import sys
import numpy as np
import tensorflow as tf

out = sys.argv[1]
v1 = tf.compat.v1
v1.disable_eager_execution()
v1.disable_resource_variables()  # genuine VariableV2 nodes, TF1-style
rng = np.random.RandomState(21)

g = v1.Graph()
with g.as_default():
    x = v1.placeholder(tf.float32, [None, 4], name="x")
    w = v1.get_variable("w", initializer=rng.randn(4, 3).astype(np.float32))
    b = v1.get_variable("b", initializer=rng.randn(3).astype(np.float32))
    h = v1.nn.relu(v1.matmul(x, w) + b)
    w2 = v1.get_variable("w2", initializer=rng.randn(3, 1).astype(np.float32))
    y = v1.math.sigmoid(v1.squeeze(v1.matmul(h, w2), -1), name="prediction")
    with v1.Session(graph=g) as sess:
        sess.run(v1.global_variables_initializer())
        assert any(v.op.type == "VariableV2" for v in v1.global_variables()), (
            "export would not exercise the TF1 ref-variable path")
        v1.saved_model.simple_save(
            sess, out, inputs={"x": x}, outputs={"prediction_node": y})
        xs = np.arange(20, dtype=np.float32).reshape(5, 4) / 10.0
        import json
        print("GOLDEN=" + json.dumps([float(v) for v in sess.run(y, {x: xs})]))
"""


def test_tf1_savedmodel_end_to_end(tmp_path):
    """A genuine TF1-format export (simple_save over VariableV2 ref
    variables) must import and serve, matching the TF1 session's forward."""
    out = tmp_path / "tf1_sm"
    r = subprocess.run(
        [sys.executable, "-c", _EXPORT_TF1, str(out)],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        pytest.skip(f"tf1 export unavailable: {r.stderr[-800:]}")
    golden_line = next(
        ln for ln in r.stdout.splitlines() if ln.startswith("GOLDEN=")
    )
    want = np.asarray(json.loads(golden_line[len("GOLDEN="):]), np.float32)
    sv = import_savedmodel(out, "graph", ModelConfig(name="T1", num_fields=4), name="T1")
    xs = np.arange(20, dtype=np.float32).reshape(5, 4) / 10.0
    got = np.asarray(sv.model.apply(sv.params, {"x": xs})["prediction_node"], np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


_EXPORT_HASHTABLE = """
import sys
import numpy as np
import tensorflow as tf

out = sys.argv[1]
rng = np.random.RandomState(31)
# Sparse catalog ids -> dense rows: the id-remap preprocessing shape
# common in real CTR exports (VERDICT r3 task 9).
keys = tf.constant([10**6, 5, 42, 10**12, 77, 3], tf.int64)
vals = tf.constant([0, 1, 2, 3, 4, 5], tf.int64)


class M(tf.Module):
    def __init__(self):
        super().__init__()
        self.table = tf.lookup.StaticHashTable(
            tf.lookup.KeyValueTensorInitializer(keys, vals), default_value=-1)
        self.emb = tf.Variable(rng.randn(7, 4).astype(np.float32), name="emb")

    @tf.function(input_signature=[
        tf.TensorSpec([None, 3], tf.int64, name="feat_ids")])
    def __call__(self, feat_ids):
        row = self.table.lookup(feat_ids)
        # Misses land on a dedicated OOV row (6).
        safe = tf.where(row < 0, tf.fill(tf.shape(row), tf.constant(6, tf.int64)), row)
        e = tf.gather(self.emb, safe)
        return {"prediction_node": tf.math.sigmoid(tf.reduce_sum(e, axis=[1, 2]))}


m = M()
tf.saved_model.save(m, out, signatures={"serving_default": m.__call__})
"""

_GOLDEN_HASHTABLE = """
import sys, json
import numpy as np
import tensorflow as tf

src = sys.argv[1]
ids = np.array([[5, 42, 999], [10**12, 3, 77], [1, 2, 10**6]], np.int64)
f = tf.saved_model.load(src).signatures["serving_default"]
out = f(feat_ids=tf.constant(ids))
print(json.dumps([float(x) for x in out["prediction_node"].numpy()]))
"""


def test_static_hashtable_export_matches_tf(tmp_path):
    """A genuine StaticHashTable export (int64 id-remap + OOV handling)
    serves natively: table contents statically resolved from the
    initializer chain, lookups as searchsorted — parity with TF's own
    forward including misses."""
    out = tmp_path / "ht_sm"
    r = subprocess.run(
        [sys.executable, "-c", _EXPORT_HASHTABLE, str(out)],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        pytest.skip(f"tensorflow export unavailable: {r.stderr[-800:]}")
    sv = import_savedmodel(out, "graph", ModelConfig(name="HT", num_fields=3), name="HT")
    ids = np.array([[5, 42, 999], [10**12, 3, 77], [1, 2, 10**6]], np.int64)
    with enable_x64():
        got = np.asarray(
            sv.model.apply(sv.params, {"feat_ids": ids})["prediction_node"],
            np.float32,
        )
    g = subprocess.run(
        [sys.executable, "-c", _GOLDEN_HASHTABLE, str(out)],
        capture_output=True, text=True, timeout=600,
    )
    assert g.returncode == 0, g.stderr[-2000:]
    want = np.asarray(json.loads(g.stdout.strip().splitlines()[-1]), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    # And under jit (the serving path), where the lookup must trace.
    with enable_x64():
        got_jit = np.asarray(
            jax.jit(sv.model.apply)(sv.params, {"feat_ids": ids})["prediction_node"],
            np.float32,
        )
    np.testing.assert_allclose(got_jit, want, rtol=2e-5, atol=1e-6)


_EXPORT_COND = """
import sys
import numpy as np
import tensorflow as tf

out = sys.argv[1]
rng = np.random.RandomState(41)


class M(tf.Module):
    def __init__(self):
        super().__init__()
        self.w = tf.Variable(rng.randn(4, 3).astype(np.float32), name="w")
        # Captured config tensor driving the branch: exported as a real
        # StatelessIf/If node (a python bool would be traced away).
        self.use_relu = tf.Variable(True, trainable=False, name="use_relu")

    @tf.function(input_signature=[tf.TensorSpec([None, 4], tf.float32, name="x")])
    def __call__(self, x):
        h = tf.matmul(x, self.w)
        h = tf.cond(self.use_relu, lambda: tf.nn.relu(h), lambda: tf.nn.tanh(h))
        return {"prediction_node": tf.reduce_sum(h, axis=1)}


m = M()
tf.saved_model.save(m, out, signatures={"serving_default": m.__call__})
import json
xs = np.arange(12, dtype=np.float32).reshape(3, 4) / 6.0 - 0.5
f = tf.saved_model.load(out).signatures["serving_default"]
print("GOLDEN=" + json.dumps([float(v) for v in f(x=tf.constant(xs))["prediction_node"].numpy()]))
"""


def test_constant_predicate_cond_export(tmp_path):
    """A genuine tf.cond export gated on a captured config variable must
    serve: the executor resolves the predicate at trace time and inlines
    the chosen branch (If/StatelessIf)."""
    out = tmp_path / "cond_sm"
    r = subprocess.run(
        [sys.executable, "-c", _EXPORT_COND, str(out)],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        pytest.skip(f"tensorflow export unavailable: {r.stderr[-800:]}")
    golden = next(l for l in r.stdout.splitlines() if l.startswith("GOLDEN="))
    want = np.asarray(json.loads(golden[len("GOLDEN="):]), np.float32)
    sv = import_savedmodel(out, "graph", ModelConfig(name="C", num_fields=4), name="C")
    xs = np.arange(12, dtype=np.float32).reshape(3, 4) / 6.0 - 0.5
    got = np.asarray(sv.model.apply(sv.params, {"x": xs})["prediction_node"], np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    # And under jit — the SERVING path, where params (and so the variable
    # read feeding the predicate) are tracers: the executor must resolve
    # the predicate from import-time values, not reject it (review
    # finding: the un-jitted assertion alone left serving broken).
    got_jit = np.asarray(
        jax.jit(sv.model.apply)(sv.params, {"x": xs})["prediction_node"],
        np.float32,
    )
    np.testing.assert_allclose(got_jit, want, rtol=2e-5, atol=1e-6)


def test_data_dependent_if_is_named():
    """An If whose predicate depends on live input stays a documented,
    node-named error under jit (no silent single-branch inlining)."""
    meta = _tiny_meta("cond:0")
    g = meta.graph_def
    red = g.node.add(); red.name = "pred"; red.op = "Any"
    red.input.extend(["x", "axes"])
    ax = g.node.add(); ax.name = "axes"; ax.op = "Const"
    ax.attr["value"].tensor.dtype = 3
    ax.attr["value"].tensor.int_val.append(0)
    ax.attr["value"].tensor.tensor_shape.dim.add().size = 1
    cond = g.node.add(); cond.name = "cond"; cond.op = "StatelessIf"
    cond.input.extend(["pred", "x"])
    fn = g.library.function.add()
    fn.signature.name = "branch"
    cond.attr["then_branch"].func.name = "branch"
    cond.attr["else_branch"].func.name = "branch"

    model, params = graph_model(meta, {}, name="dd")
    with pytest.raises(UnsupportedOpError, match="data-dependent"):
        jax.jit(lambda p, b: model.apply(p, b))(
            params, {"x": np.ones((2, 2), np.float32) > 0}
        )


def test_unresolvable_table_is_named():
    """A find against a table with no statically resolvable contents must
    raise the documented UnsupportedOpError naming the node, not a shape
    error."""
    meta = _tiny_meta("find:0")
    g = meta.graph_def
    t = g.node.add(); t.name = "tbl"; t.op = "HashTableV2"
    f = g.node.add(); f.name = "dflt"; f.op = "Const"
    # A float Const we never wire as the table's initializer.
    f.attr["value"].tensor.dtype = 1
    f.attr["value"].tensor.float_val.append(-1.0)
    find = g.node.add(); find.name = "find"; find.op = "LookupTableFindV2"
    find.input.extend(["tbl", "x", "dflt"])

    model, params = graph_model(meta, {}, name="tbl_test")
    with pytest.raises(UnsupportedOpError, match="find.*statically resolvable"):
        model.apply(params, {"x": np.ones((2, 2), np.float32)})


def test_executor_rejects_unknown_signature(exotic_export):
    meta = serve_meta_graph(read_saved_model(exotic_export))
    with pytest.raises(Exception, match="nope"):
        GraphExecutor(meta, "nope")


_EXPORT_CUSTOM_SIG = """
import sys
import numpy as np
import tensorflow as tf

out = sys.argv[1]
rng = np.random.RandomState(3)


class Tiny(tf.Module):
    def __init__(self):
        super().__init__()
        self.w = tf.Variable(rng.randn(4, 1).astype(np.float32), name="w")

    @tf.function(input_signature=[tf.TensorSpec([None, 4], tf.float32, name="x")])
    def score(self, x):
        return {"prediction_node": tf.squeeze(tf.sigmoid(tf.matmul(x, self.w)), -1)}


m = Tiny()
tf.saved_model.save(m, out, signatures={"score": m.score})
"""


def test_graph_import_without_serving_default(tmp_path):
    """An export whose only signature has a custom name must thread that ONE
    name through extraction, executor build, and the dry-run probe."""
    out = tmp_path / "custom_sig"
    r = subprocess.run(
        [sys.executable, "-c", _EXPORT_CUSTOM_SIG, str(out)],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        pytest.skip(f"tensorflow export unavailable: {r.stderr[-800:]}")
    sv = import_savedmodel(out, "graph", ModelConfig(name="T", num_fields=4), name="T")
    x = np.random.RandomState(1).rand(5, 4).astype(np.float32)
    got = np.asarray(sv.model.apply(sv.params, {"x": x})["prediction_node"])
    assert got.shape == (5,) and np.all((got > 0) & (got < 1))


_EXPORT_KERAS = """
import sys
import numpy as np
import tensorflow as tf

out = sys.argv[1]
rng = np.random.RandomState(4)
tf.keras.utils.set_random_seed(4)

inp_ids = tf.keras.Input(shape=(5,), dtype=tf.int64, name="feat_ids")
inp_wts = tf.keras.Input(shape=(5,), dtype=tf.float32, name="feat_wts")
folded = tf.keras.layers.Lambda(
    lambda t: tf.math.floormod(t, 733), output_shape=(5,)
)(inp_ids)
emb = tf.keras.layers.Embedding(733, 6)(folded)
weighted = tf.keras.layers.Multiply()([emb, tf.keras.layers.Reshape((5, 1))(inp_wts)])
flat = tf.keras.layers.Flatten()(weighted)
h = tf.keras.layers.Dense(16, activation="relu")(flat)
h = tf.keras.layers.Dense(8, activation="tanh")(h)
p = tf.keras.layers.Dense(1, activation="sigmoid", name="out")(h)
p = tf.keras.layers.Reshape(())(p)
model = tf.keras.Model([inp_ids, inp_wts], {"prediction_node": p})

@tf.function(input_signature=[
    tf.TensorSpec([None, 5], tf.int64, name="feat_ids"),
    tf.TensorSpec([None, 5], tf.float32, name="feat_wts"),
])
def serve(feat_ids, feat_wts):
    return model([feat_ids, feat_wts])

tf.saved_model.save(model, out, signatures={"serving_default": serve})
"""

_GOLDEN_KERAS = """
import sys, json
import numpy as np
import tensorflow as tf

src, seed, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
rng = np.random.RandomState(seed)
ids = rng.randint(0, 1 << 40, size=(n, 5)).astype(np.int64)
wts = rng.rand(n, 5).astype(np.float32)
f = tf.saved_model.load(src).signatures["serving_default"]
out = f(feat_ids=tf.constant(ids), feat_wts=tf.constant(wts))
print(json.dumps([float(x) for x in out["prediction_node"].numpy()]))
"""


def test_keras_export_serves_via_graph_executor(tmp_path):
    """A genuine tf.keras functional model (Embedding/Dense/Lambda/Multiply
    stack) — the most common real-world export shape — must serve via the
    graph executor and match Keras's own forward."""
    out = tmp_path / "keras_sm"
    r = subprocess.run(
        [sys.executable, "-c", _EXPORT_KERAS, str(out)],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode != 0:
        pytest.skip(f"keras export unavailable: {r.stderr[-800:]}")
    sv = import_savedmodel(out, "graph", ModelConfig(name="K", num_fields=5), name="K")
    rng = np.random.RandomState(8)
    arrays = {
        "feat_ids": rng.randint(0, 1 << 40, size=(7, 5)).astype(np.int64),
        "feat_wts": rng.rand(7, 5).astype(np.float32),
    }
    with enable_x64():
        got = np.asarray(sv.model.apply(sv.params, arrays)["prediction_node"], np.float32)
    g = subprocess.run(
        [sys.executable, "-c", _GOLDEN_KERAS, str(out), "8", "7"],
        capture_output=True, text=True, timeout=600,
    )
    assert g.returncode == 0, g.stderr[-2000:]
    want = np.asarray(json.loads(g.stdout.strip().splitlines()[-1]), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
