"""Multi-device correctness, run in subprocesses with 8 fake host devices
(so this process's single-device jax init stays clean).

Each scenario asserts the SHARDED computation equals its single-device
reference: that's the strongest evidence the production sharding config is
semantically sound, short of real hardware.
"""
import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str):
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.distributed import make_mesh
        """
    ) + textwrap.dedent(body)
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=SRC),
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_fakewords_search_equals_single_device():
    run_subprocess("""
    from repro.core import bruteforce, distributed, fakewords
    from repro.core.types import FakeWordsConfig
    rng = np.random.default_rng(0)
    vecs = jnp.asarray(rng.normal(size=(1024, 32)).astype(np.float32))
    qs = vecs[:8]
    cfg = FakeWordsConfig(quantization=50)
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    idx_sh = distributed.build_sharded(mesh, vecs, cfg, ("data", "model"))
    search = distributed.make_sharded_search(mesh, cfg, ("data", "model"), k=10, depth=50, rerank=True)
    q_tf = fakewords.encode_queries(qs, cfg)
    s_sh, i_sh = search(idx_sh, q_tf, bruteforce.l2_normalize(qs))
    # single-device reference
    idx = fakewords.build(vecs, cfg)
    s_1, i_1 = fakewords.search(idx, q_tf, bruteforce.l2_normalize(qs), k=10, depth=50, rerank=True)
    # idf must match exactly (psum'd df == global df)
    np.testing.assert_allclose(np.asarray(idx_sh.idf), np.asarray(idx.idf), rtol=1e-6)
    from repro.core import eval as ev
    ov = float(ev.overlap(i_1, i_sh))
    assert ov > 0.95, f"overlap {ov}"
    print("sharded search ok", ov)
    """)


def test_sharded_blockmax_search_and_rerank_padding_mask():
    run_subprocess("""
    from repro.core import blockmax, bruteforce, distributed, fakewords
    from repro.core import eval as ev
    from repro.core.types import FakeWordsConfig
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(1024, 32)).astype(np.float32)
    q = rng.normal(size=(1, 32)).astype(np.float32)
    # plant shard-local doc 0 == the query on EVERY shard: with the old
    # unmasked rerank, -1 padding slots gathered local doc 0 and returned
    # perfect cosine scores under fake ids (-1 + shard * n_local)
    for sh in range(8):
        vecs[sh * 128] = q[0]
    vecs = jnp.asarray(vecs)
    cfg = FakeWordsConfig(quantization=50)
    mesh = jax.make_mesh((8,), ("data",))
    # deprecated alias of the generic BuildPipeline build_sharded
    idx_sh = distributed.build_fakewords_sharded(mesh, vecs, cfg, ("data",))
    # ragged per-shard blocks: 128 docs/shard, block 48 -> 3 blocks, 16 pad
    bm_sh = distributed.build_blockmax_sharded(mesh, idx_sh, ("data",), block_size=48)
    assert bm_sh.ub.shape[0] == 24 and bm_sh.mode == "classic"
    qn = bruteforce.l2_normalize(jnp.asarray(q))
    q_tf = fakewords.encode_queries(qn, cfg)
    # depth > n_local AND all blocks kept: every shard deterministically
    # returns 16 padded (-1) slots into the rerank + merge
    search = distributed.make_sharded_search(
        mesh, cfg, ("data",), k=20, depth=200, rerank=True, blockmax_keep=3)
    s, i = search(idx_sh, bm_sh, q_tf, qn)
    ii, ss = np.asarray(i)[0], np.asarray(s)[0]
    assert ((ii >= -1) & (ii < 1024)).all()
    # exactly the 8 planted docs earn ~1.0; fake ids 127, 255, ... must not
    planted = set(range(0, 1024, 128))
    assert set(ii[ss > 0.999].tolist()) == planted, ii[ss > 0.999]
    # every returned score must be the true cosine of its claimed doc id
    vn = np.asarray(bruteforce.l2_normalize(vecs)); qv = np.asarray(qn)[0]
    for idd, sc in zip(ii, ss):
        if idd >= 0:
            np.testing.assert_allclose(sc, qv @ vn[idd], rtol=1e-4, atol=1e-5)
    # keep-all blockmax matches the dense sharded search results
    idx = fakewords.build(vecs, cfg)
    s1, i1 = fakewords.search(idx, q_tf, qn, k=20, depth=200, rerank=True)
    ov = float(ev.overlap(i1, jnp.asarray(ii[None, :])))
    assert ov > 0.9, ov
    print("sharded blockmax ok", ov)
    """)


def test_sharded_filtered_search_equals_local_filtered():
    run_subprocess("""
    from repro.core import bruteforce, distributed, fakewords
    from repro.core import pipeline as pl
    from repro.core.types import FakeWordsConfig
    rng = np.random.default_rng(5)
    vecs = jnp.asarray(rng.normal(size=(1024, 32)).astype(np.float32))
    qs = vecs[:8]
    cfg = FakeWordsConfig(quantization=50)
    mesh = jax.make_mesh((8,), ("data",))
    idx_sh = distributed.build_sharded(mesh, vecs, cfg, ("data",))
    search = distributed.make_sharded_search(
        mesh, cfg, ("data",), k=10, depth=64, rerank=True, filtered=True)
    qn = bruteforce.l2_normalize(qs)
    q_tf = fakewords.encode_queries(qn, cfg)
    idx = fakewords.build(vecs, cfg)
    matcher = pl.make_matcher(cfg)
    for ratio in (0.01, 0.1, 0.5):
        m = (rng.random(1024) < ratio).astype(np.int32)
        m[:16] = 1  # guarantee >= k survivors
        filt = jnp.asarray(m)
        s_sh, i_sh = search(idx_sh, q_tf, qn, filt)
        # local reference: the same one-pass in-match filter
        s_l, i_l = pl.match_rerank(matcher, idx, q_tf, qn, k=10, depth=64,
                                   rerank=True, filt=filt)
        np.testing.assert_array_equal(np.asarray(i_sh), np.asarray(i_l))
        assert ((np.asarray(i_sh) < 0) |
                (m[np.maximum(np.asarray(i_sh), 0)] != 0)).all()
    # all-ones == the unfiltered sharded search bit-for-bit
    plain = distributed.make_sharded_search(
        mesh, cfg, ("data",), k=10, depth=64, rerank=True)
    s0, i0 = plain(idx_sh, q_tf, qn)
    s1, i1 = search(idx_sh, q_tf, qn, jnp.ones((1024,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    # all-zeros: padded, never NaN
    s2, i2 = search(idx_sh, q_tf, qn, jnp.zeros((1024,), jnp.int32))
    assert (np.asarray(i2) == -1).all() and not np.isnan(np.asarray(s2)).any()
    print("sharded filtered ok")
    """)


def test_sharded_gnn_full_graph_equals_single_device():
    run_subprocess("""
    from repro.models import gnn
    from repro.data import graph as gd
    g = gd.make_graph(gd.GraphConfig(n_nodes=200, n_edges=800, d_feat=16, n_classes=5))
    src, dst = g.edge_list()
    cfg = gnn.SageConfig(n_layers=2, d_in=16, d_hidden=32, n_classes=5, fanouts=(5, 3))
    params = gnn.init_params(jax.random.key(0), cfg)
    mask = jnp.ones((200,), jnp.float32)
    ref = gnn.loss_full(params, g.feats, src, dst, g.labels, mask, cfg)
    mesh = make_mesh((8,), ("dev",))
    # shard edges over all devices (uneven 800/8 is fine)
    es = NamedSharding(mesh, P("dev"))
    srcs = jax.device_put(src, es); dsts = jax.device_put(dst, es)
    out = jax.jit(gnn.loss_full, static_argnames="cfg")(params, g.feats, srcs, dsts, g.labels, mask, cfg)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-5)
    print("gnn sharded ok", float(out))
    """)


def test_sharded_recsys_table_equals_single_device():
    run_subprocess("""
    from repro.models import recsys as rec
    table_spec = rec.TableSpec(rec.criteo_row_counts(8, 4096), 16)
    cfg = rec.RecsysConfig(model="deepfm", table=table_spec, mlp=(32, 32))
    params = rec.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    rows = np.asarray(table_spec.row_counts)
    idx = jnp.asarray(rng.integers(0, rows[None, :, None], (16, 8, 1)), jnp.int32)
    ref = rec.forward(params, cfg, idx)
    mesh = make_mesh((2, 4), ("data", "model"))
    p_sh = dict(params)
    p_sh["table"] = jax.device_put(params["table"], NamedSharding(mesh, P("model", None)))
    p_sh["linear"] = jax.device_put(params["linear"], NamedSharding(mesh, P("model", None)))
    idx_sh = jax.device_put(idx, NamedSharding(mesh, P("data", None, None)))
    out = jax.jit(lambda p, i: rec.forward(p, cfg, i))(p_sh, idx_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)
    print("recsys sharded ok")
    """)


def test_sharded_lm_train_step_equals_single_device():
    run_subprocess("""
    import dataclasses
    from repro.models import transformer as tfm
    cfg = tfm.TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                                d_ff=128, vocab=128, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 128)
    ref = tfm.loss_fn(params, toks, toks, cfg)
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg_sh = dataclasses.replace(cfg, batch_axes=("data",), tp_axis="model")
    from repro.sharding import rules
    specs = rules.lm_param_specs(tfm.param_shapes(cfg))
    p_sh = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                        params, specs, is_leaf=lambda x: hasattr(x, "shape"))
    t_sh = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, t: tfm.loss_fn(p, t, t, cfg_sh))(p_sh, t_sh)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-4)
    print("lm sharded loss ok", float(out), float(ref))
    """)


def test_compressed_allreduce_and_gpipe():
    run_subprocess("""
    from repro.train import compression, pipeline
    mesh = jax.make_mesh((8,), ("data",))
    g = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100.0
    def f(gs, r):
        return compression.compressed_psum(gs, r, "data")
    out, new_r = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P("data"))))({"w": g}, {"w": jnp.zeros((8, 64))})
    exact = jnp.mean(g, axis=0)
    err = float(jnp.max(jnp.abs(out["w"].reshape(-1, 64)[0] - exact)))
    assert err < 5e-3 * float(jnp.max(jnp.abs(exact))) + 1e-4, err
    # error feedback: residual equals quantization error
    assert new_r["w"].shape == (8, 64)

    n_layers, d, M, mb = 8, 16, 4, 2
    ws = jax.random.normal(jax.random.key(0), (n_layers, d, d)) * (1.0 / np.sqrt(d))
    x = jax.random.normal(jax.random.key(1), (M, mb, d))
    layer_fn = lambda h, w: jnp.tanh(h @ w)
    mesh_p = jax.make_mesh((4,), ("pipe",))
    out_p = jax.jit(pipeline.build_gpipe_fn(mesh_p, layer_fn, n_stages=4))(ws, x)
    ref = x
    for i in range(n_layers):
        ref = layer_fn(ref, ws[i])
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref), atol=1e-6)
    print("compression + gpipe ok")
    """)


def test_elastic_checkpoint_restore_across_meshes():
    run_subprocess("""
    import tempfile
    from repro.train import checkpoint as ckpt
    state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    mesh_a = jax.make_mesh((8,), ("data",))
    sharded = {"w": jax.device_put(state["w"], NamedSharding(mesh_a, P("data", None)))}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, sharded)
        # restore onto a DIFFERENT mesh shape (elastic restart)
        mesh_b = jax.make_mesh((2, 4), ("x", "y"))
        out, step = ckpt.restore(
            d, state,
            sharding_fn=lambda k, a: NamedSharding(mesh_b, P("x", "y")))
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(state["w"]))
        assert out["w"].sharding.mesh.shape == {"x": 2, "y": 4}
    print("elastic restore ok")
    """)
