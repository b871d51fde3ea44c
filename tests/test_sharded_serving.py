"""Multi-device serving tests on 8 simulated CPU devices (DESIGN.md §11).

Subprocess-per-test like tests/test_parallel.py: the main pytest process
must keep seeing 1 CPU device, so each test exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before importing
jax in a child interpreter.

The contract under test is exactness, not tolerance: sharded packing
equals pack-then-shard bit-for-bit, the fused sharded GEMM (column- and
row-parallel, folded psum) equals ``dsbp_matmul_ref`` bit-for-bit, and
``Engine.serve`` emits token-for-token the same stream on a (1,1) mesh,
a (2,4) mesh and no mesh at all.
"""
import subprocess
import sys
import textwrap


def _run(body: str):
    src = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        """
    ) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, timeout=560, cwd=".")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_sharded_pack_equals_pack_then_shard():
    """pack_weights_sharded == pack_weights bit-for-bit (per-column weight
    scale granularity makes the weight path independent per output column),
    and per-tensor granularity / indivisible N fall back cleanly."""
    _run("""
    from repro.core.quantized import PRESETS, pack_weights
    from repro.core.packed import pack_weights_sharded

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    for shape in [(256, 128), (128, 512), (3, 128, 256)]:  # incl. stacked lead
        w = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        pg = pack_weights(w, PRESETS["precise"])
        ps = pack_weights_sharded(w, PRESETS["precise"], mesh)
        for f in ("ka", "kscale", "tscale", "bits"):
            a, b = np.asarray(getattr(pg, f)), np.asarray(getattr(ps, f))
            assert a.shape == b.shape and np.array_equal(a, b), (shape, f)
        assert (ps.k, ps.n, ps.group_size) == (pg.k, pg.n, pg.group_size)
    # indivisible N (130 % 4 != 0) falls back to the global pack
    w = jnp.asarray(rng.normal(size=(128, 130)).astype(np.float32))
    ps = pack_weights_sharded(w, PRESETS["precise"], mesh)
    pg = pack_weights(w, PRESETS["precise"])
    assert np.array_equal(np.asarray(ps.ka), np.asarray(pg.ka))
    print("pack equality OK")
    """)


def test_fused_sharded_gemm_bit_exact_vs_ref():
    """Column-parallel, row-parallel (folded psum) and fallback paths of
    dsbp_matmul_fused_sharded are all bit-exact vs dsbp_matmul_ref."""
    _run("""
    from repro.core.quantized import PRESETS, pack_weights, dsbp_matmul_ref
    from repro.core.packed import pack_weights_sharded
    from repro.kernels import ops as kops

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
    rng = np.random.default_rng(1)
    cfg = PRESETS["precise"]
    w = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    pw = pack_weights_sharded(w, cfg, mesh)
    x = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    ref = np.asarray(dsbp_matmul_ref(x, w, cfg))
    fused = np.asarray(kops.dsbp_matmul_fused(x, pack_weights(w, cfg)))
    assert np.array_equal(fused, ref)
    for axes in [dict(k_axis=None, n_axis="model"),      # column-parallel
                 dict(k_axis="model", n_axis=None),      # row-parallel psum
                 dict(k_axis="data", n_axis="model")]:   # 2-D K x N split
        y = np.asarray(kops.dsbp_matmul_fused_sharded(
            x, pw, mesh, batch_axis=None, **axes))
        assert np.array_equal(y, ref), axes
    # batch rows over 'data' on top of column-parallel TP
    y = np.asarray(kops.dsbp_matmul_fused_sharded(
        x, pw, mesh, batch_axis=("data",), k_axis=None, n_axis="model"))
    assert np.array_equal(y, ref)
    # fallback: K' shards not group-aligned (192/(64*4)), ragged M
    w2 = jnp.asarray(rng.normal(size=(192, 96)).astype(np.float32))
    pw2 = pack_weights_sharded(w2, cfg, mesh)
    x2 = jnp.asarray(rng.normal(size=(3, 192)).astype(np.float32))
    y2 = np.asarray(kops.dsbp_matmul_fused_sharded(
        x2, pw2, mesh, batch_axis=("data",), k_axis="model", n_axis=None))
    assert np.array_equal(y2, np.asarray(dsbp_matmul_ref(x2, w2, cfg)))
    print("fused sharded bit-exact OK")
    """)


def test_serve_parity_yi_mesh_vs_single():
    """Engine.serve (ragged mix) is token-for-token identical with no mesh,
    a (1,1) mesh and a (2,4) mesh, on the quantized attention arch.
    n_heads=8 makes wo's K' (256) group-aligned across model=4, so the
    row-parallel folded-psum path actually executes."""
    _run("""
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    cfg = smoke_config("yi-9b").replace(remat=False, quant="precise",
                                        n_heads=8)
    params = M.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, (int(l),))
            for l in (5, 11, 3, 8, 14, 6)]

    outs = {}
    for tag, kw in {
        "none": dict(),
        "1x1": dict(mesh_shape=(1, 1), per_device_batch_size=4),
        "2x4": dict(mesh_shape=(2, 4), per_device_batch_size=1),
    }.items():
        eng = Engine(params, cfg, ServeConfig(max_len=64, batch_size=4, **kw))
        outs[tag] = eng.serve(reqs, max_new_tokens=6)
        if kw.get("mesh_shape") == (2, 4):
            assert eng.pool_size == 8, eng.pool_size
            assert eng.cfg.quant_method == "dsbp_fused_sharded"
    for uid in outs["none"]:
        a = outs["none"][uid]
        assert np.array_equal(a, outs["1x1"][uid]), (uid, "1x1")
        assert np.array_equal(a, outs["2x4"][uid]), (uid, "2x4")
    print("yi serve parity OK")
    """)


def test_serve_parity_spec_decode_under_mesh():
    """The self-speculative round (draft + verify + rollback) runs entirely
    under the mesh and still matches the single-device spec stream."""
    _run("""
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    cfg = smoke_config("yi-9b").replace(remat=False, quant="precise",
                                        n_heads=8)
    params = M.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    reqs = [rng.integers(0, cfg.vocab_size, (int(l),)) for l in (7, 4, 12, 9)]
    kw = dict(max_len=64, batch_size=4, spec_k=3)
    out_1 = Engine(params, cfg, ServeConfig(**kw)).serve(reqs, max_new_tokens=6)
    eng = Engine(params, cfg, ServeConfig(**kw, mesh_shape=(2, 4)))
    out_8 = eng.serve(reqs, max_new_tokens=6)
    assert eng.last_stats["spec_rounds"] > 0
    for uid in out_1:
        assert np.array_equal(out_1[uid], out_8[uid]), uid
    print("spec serve parity OK")
    """)


def test_serve_parity_mixtral_expert_axis():
    """MoE serving parity on a (2,2,2) data x model x expert mesh: expert
    stacks shard their leading E dim, the rest of the TP plan unchanged."""
    _run("""
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    cfg = smoke_config("mixtral-8x7b").replace(remat=False, quant="precise")
    params = M.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    reqs = [rng.integers(0, cfg.vocab_size, (int(l),)) for l in (6, 10, 4)]
    out_1 = Engine(params, cfg, ServeConfig(max_len=64, batch_size=4)).serve(
        reqs, max_new_tokens=5)
    eng = Engine(params, cfg, ServeConfig(
        max_len=64, batch_size=4, mesh_shape=(2, 2, 2),
        mesh_axes=("data", "model", "expert"), per_device_batch_size=1))
    assert eng.pool_size == 8
    out_8 = eng.serve(reqs, max_new_tokens=5)
    for uid in out_1:
        assert np.array_equal(out_1[uid], out_8[uid]), uid
    print("mixtral serve parity OK")
    """)


def test_serve_container_shards_and_no_relayout():
    """The engine's packed containers live at their compute layout (serve
    pspecs) — wq column shards over 'model', w2 K-row shards — and the
    sharded fused GEMM keeps the no-relayout contract
    (count_weight_transposes == 0)."""
    _run("""
    from repro.configs import smoke_config
    from repro.core.packed import PackedDSBPWeight
    from repro.core.quantized import PRESETS
    from repro.kernels import ops as kops
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    cfg = smoke_config("yi-9b").replace(remat=False, quant="precise",
                                        n_heads=8)
    params = M.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, ServeConfig(max_len=64, batch_size=4,
                                          mesh_shape=(2, 4)))
    mesh = eng.mesh
    wq = eng.params["units"][0]["attn"]["wq"]  # column-parallel plan
    w2 = eng.params["units"][0]["ffn"]["w2"]   # row-parallel plan
    assert isinstance(wq, PackedDSBPWeight)
    def spec_of(arr):
        return arr.sharding.spec
    assert spec_of(wq.ka)[-1] == "model", spec_of(wq.ka)       # N shards
    assert spec_of(w2.ka)[-2] == "model", spec_of(w2.ka)       # K' shards
    assert spec_of(w2.tscale) == P(None, None, None), spec_of(w2.tscale)

    # no per-call weight relayout through the sharded call
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    from repro.core.packed import pack_weights_sharded
    pw = pack_weights_sharded(w, PRESETS["precise"], mesh)
    x = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    for axes in [dict(k_axis=None, n_axis="model"),
                 dict(k_axis="model", n_axis=None)]:
        n_t = kops.count_weight_transposes(
            lambda x, pw: kops.dsbp_matmul_fused_sharded(
                x, pw, mesh, batch_axis=None, **axes),
            x, pw, min_size=w.size // 2)
        assert n_t == 0, (axes, n_t)
    print("layout + no-relayout OK")
    """)


def test_serve_parity_paged_vs_dense_under_mesh():
    """Paged serving (block pool + tables + prefix sharing) emits exactly
    the dense engine's tokens AT THE SAME topology, both on one device and
    on the (2,4) mesh — the block pool shards over the batch axes
    (parallel.sharding.cache_pspecs paged rule) and GSPMD turns the table
    gathers into collectives.  (Mesh-vs-single is compared per ENGINE, the
    same contract the dense parity test asserts: collectives reorder float
    sums, so cross-topology equality is a property of the model, not of
    the paged cache.)"""
    _run("""
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    cfg = smoke_config("yi-9b").replace(remat=False)
    params = M.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, (8,))
    reqs = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, (3,))])
            for _ in range(4)] + [rng.integers(0, cfg.vocab_size, (6,))]
    pkw = dict(batch_size=2, max_len=32, prefill_bucket=8, paged=True,
               kv_block_size=4, max_active=4)
    for mesh_shape in (None, (2, 4)):
        d = Engine(params, cfg, ServeConfig(batch_size=4, max_len=32,
                                            prefill_bucket=8,
                                            mesh_shape=mesh_shape))
        od = d.serve(reqs, max_new_tokens=6)
        p = Engine(params, cfg, ServeConfig(**pkw, mesh_shape=mesh_shape))
        op = p.serve(reqs, max_new_tokens=6)
        for k in od:
            assert np.array_equal(od[k], op[k]), (mesh_shape, k, od[k], op[k])
        assert p.last_stats["prefix_hit_blocks"] > 0
        assert p.last_stats["stalled_decode_steps"] == 0
    print("paged-vs-dense parity OK on 1 device and (2,4) mesh")
    """)


def test_init_packed_on_mesh_equals_packing_the_float_model():
    """Layer-by-layer build on a 4-device (data=1, model=4) mesh: every
    projection packs through pack_weights_sharded into column shards, and
    the result equals pack_weights_int8(M.init(...)) on one device."""
    _run("""
    from repro.configs import smoke_config
    from repro.core.packed import PackedDSBPWeight
    from repro.models import model as M
    from repro.parallel.sharding import make_mesh
    from repro.serve.engine import init_packed, pack_weights_int8

    cfg = smoke_config("yi-9b").replace(quant="precise")
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    key = jax.random.PRNGKey(5)
    want, want_stats = pack_weights_int8(M.init(key, cfg), "precise")
    got, got_stats = init_packed(key, cfg, "precise", mesh=mesh)
    is_pw = lambda x: isinstance(x, PackedDSBPWeight)
    assert (jax.tree.structure(got, is_leaf=is_pw)
            == jax.tree.structure(want, is_leaf=is_pw))
    sharded = 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
        sharded += len(a.sharding.device_set) == 4
    assert sharded > 0, "no leaf was packed into shards"
    assert got_stats == want_stats
    print("mesh init_packed OK")
    """)
