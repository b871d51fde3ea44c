"""Distribution tests on 8 simulated devices (subprocess: the main test
process must keep seeing 1 CPU device — per the brief, only the dry-run
sets the 512-device flag globally)."""
import subprocess
import sys
import textwrap

import pytest


def _run(body: str):
    src = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
        """
    ) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, timeout=560, cwd=".")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """pjit'ed train step on a 4x2 mesh == single-device step, bitwise-ish."""
    _run("""
    from functools import partial
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel import sharding as SH
    from repro.train.trainer import train_step

    cfg = smoke_config("yi-9b").replace(n_layers=2, remat=False)
    ocfg = adamw.AdamWConfig()
    params = M.init(jax.random.PRNGKey(0), cfg)
    opt = adamw.init_state(params, ocfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)))}

    p1, o1, m1 = jax.jit(partial(train_step, cfg=cfg, opt_cfg=ocfg))(params, opt, batch)

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    p_sh = SH.named(mesh, SH.param_pspecs(params, mesh))
    o_sh = SH.named(mesh, {"step": P(), "m": SH.param_pspecs(params, mesh),
                           "v": SH.param_pspecs(params, mesh)})
    b_sh = SH.named(mesh, SH.batch_pspecs(batch, mesh))
    with mesh:
        p2, o2, m2 = jax.jit(partial(train_step, cfg=cfg, opt_cfg=ocfg),
                             in_shardings=(p_sh, o_sh, b_sh))(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4)
    print("sharded == single OK")
    """)


def test_pipeline_parallel_matches_sequential():
    _run("""
    from repro.parallel.pipeline import pipeline_apply, bubble_fraction

    n_stages, n_micro, mb, d = 8, 16, 4, 32
    mesh = jax.make_mesh((8,), ("pipe",))
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.standard_normal((n_stages, d, d)).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.standard_normal((n_micro, mb, d)).astype(np.float32))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    out = pipeline_apply(stage_fn, ws, x, mesh, axis="pipe")
    # sequential reference
    ref = x
    for s in range(n_stages):
        ref = jax.vmap(lambda h: stage_fn(ws[s], h))(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert 0 < bubble_fraction(n_micro, n_stages) < 0.5
    print("pipeline == sequential OK")
    """)


def test_compressed_psum_matches_plain_within_tolerance():
    _run("""
    from functools import partial
    from jax import shard_map
    from repro.train.grad_compress import psum_compressed

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((8, 512)).astype(np.float32) * 0.01)

    def f(gs):
        red, res = psum_compressed(gs[0], "data")
        return red[None], res[None]

    red, res = shard_map(f, mesh=mesh, in_specs=P("data"),
                         out_specs=(P("data"), P("data")))(g)
    plain = jnp.mean(g, axis=0)
    # single-shot error ~ e4m3 precision (2**-4 of the block amax); the
    # error-feedback residual cancels it across steps (test_substrate)
    tol = float(jnp.abs(g).max()) * 2.0**-3
    for i in range(8):
        np.testing.assert_allclose(np.asarray(red[i]), np.asarray(plain),
                                   atol=tol)
    print("compressed psum OK")
    """)


def test_expert_parallel_moe_shard_map():
    """EP: experts sharded over a dedicated axis via shard_map; matches the
    single-device grouped-dispatch MoE."""
    _run("""
    from functools import partial
    from jax import shard_map
    from repro.configs import smoke_config
    from repro.models import moe as MOE

    cfg = smoke_config("mixtral-8x7b").replace(n_experts=8, moe_group=32)
    params = MOE.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 16, cfg.d_model)).astype(np.float32))
    ref = MOE.moe_ffn(params, x, cfg, no_drop=True)

    mesh = jax.make_mesh((8,), ("expert",), axis_types=(AxisType.Auto,))
    # shard expert-leading params over the expert axis; replicate x;
    # each member computes its experts' contribution, psum combines.
    def ep_moe(p_local, xx):
        eid = jax.lax.axis_index("expert")
        logits = xx @ p_local["router"]          # router replicated
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, cfg.top_k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        mine = jnp.zeros(xx.shape[:-1], jnp.float32)
        out = jnp.zeros_like(xx)
        for c in range(cfg.top_k):
            sel = (idx[..., c] == eid).astype(xx.dtype)
            h1 = jnp.einsum("bsd,df->bsf", xx, p_local["w1"][0])
            h3 = jnp.einsum("bsd,df->bsf", xx, p_local["w3"][0])
            h = jax.nn.silu(h1) * h3
            y = jnp.einsum("bsf,fd->bsd", h, p_local["w2"][0])
            out = out + y * (sel * gate[..., c])[..., None]
        return jax.lax.psum(out, "expert")

    ep = shard_map(ep_moe, mesh=mesh,
                   in_specs=({"router": P(), "w1": P("expert"), "w3": P("expert"),
                              "w2": P("expert")}, P()),
                   out_specs=P())
    got = ep({"router": params["router"], "w1": params["w1"],
              "w3": params["w3"], "w2": params["w2"]}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)
    print("expert parallel OK")
    """)


def test_long_context_sequence_sharded_decode_attention():
    """SP: KV cache sequence-sharded over 'data'; decode attention must
    equal the unsharded result (softmax over a sharded axis -> collectives)."""
    _run("""
    from repro.models.attention import decode_attention

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 4, 1, 32)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 32)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 32)).astype(np.float32))
    ref = decode_attention(q, k, v, jnp.int32(400))

    from jax.sharding import NamedSharding
    ksh = jax.device_put(k, NamedSharding(mesh, P(None, None, "data", None)))
    vsh = jax.device_put(v, NamedSharding(mesh, P(None, None, "data", None)))
    with mesh:
        got = jax.jit(decode_attention, static_argnames=("window",))(
            q, ksh, vsh, jnp.int32(400))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    print("sequence-sharded decode OK")
    """)
