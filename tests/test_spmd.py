"""SPMD collective pipeline — runs in a subprocess with 8 fake devices
(the main test process must keep the single real CPU device)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, %r)
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.pipeline.spmd import init_pipeline_params, make_spmd_train_loss
    from repro.models.blocks import apply_layer
    from repro.models.layers import apply_norm, embed, unembed

    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              num_layers=4, dtype="float32")
    p = 4
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(2, 4)
    params = init_pipeline_params(jax.random.PRNGKey(0), cfg, p)
    B, s, m = 8, 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, s+1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def ref_loss(params, batch):
        x = embed(params["embed"], batch["tokens"], cfg)
        b_, s_ = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s_, dtype=jnp.int32)[None], (b_, s_))
        kinds = cfg.layer_kinds()
        per = cfg.num_layers // p
        for i in range(p):
            for j in range(per):
                lp = jax.tree.map(lambda a: a[i], params["stages"][j])
                x, _ = apply_layer(lp, x, cfg, kinds[j], pos)
        x = apply_norm(params["final_norm"], x)
        logits = unembed(params["embed"], x, cfg)
        lbl = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(lbl,0)[..., None], -1)[..., 0]
        return jnp.mean(nll)

    with jax.set_mesh(mesh):
        for bpipe in (False, True):
            lossf = make_spmd_train_loss(cfg, mesh, p, num_micro=m, bpipe_stash=bpipe)
            loss = jax.jit(lossf)(params, batch)
            rl_ = ref_loss(params, batch)
            assert abs(float(loss - rl_)) < 1e-5, (bpipe, float(loss), float(rl_))
            g = jax.jit(jax.grad(lossf))(params, batch)
            gr = jax.grad(ref_loss)(params, batch)
            err = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)))
            assert err < 1e-5, (bpipe, err)
            txt = jax.jit(jax.grad(lossf)).lower(params, batch).compile().as_text()
            n_cp = txt.count("collective-permute")
            if bpipe:
                assert n_cp > n_plain
            else:
                n_plain = n_cp
    print("SPMD_OK")
""") % SRC


@pytest.mark.slow
def test_spmd_pipeline_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600)
    assert "SPMD_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# BPipe eviction permutation: a device-level involution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("p", [2, 4, 5, 8])
def test_bpipe_perms_round_trip(p):
    """The EVICT permutation must be its own inverse (the LOAD hop uses
    the same pairs), cover every device exactly once, and connect
    exactly the (x, p-1-x) pairs of the paper's Fig. 2."""
    from repro.core.schedule import bpipe_pairs
    from repro.pipeline.spmd import _bpipe_perms
    perm_out, perm_back = _bpipe_perms(p)
    assert perm_out == perm_back                 # involution: EVICT == LOAD
    fwd = dict(perm_out)
    assert len(fwd) == len(perm_out) == p        # total, no duplicates
    assert sorted(fwd) == sorted(fwd.values()) == list(range(p))
    for src, dst in fwd.items():
        assert fwd[dst] == src                   # applying twice = identity
    want = set()
    for a, b in bpipe_pairs(p):
        want |= {(a, b), (b, a)}
    if p % 2:
        want.add((p // 2, p // 2))               # odd middle stage self-maps
    assert set(perm_out) == want


# ---------------------------------------------------------------------------
# hop_distance on a non-contiguous stage -> device layout
# ---------------------------------------------------------------------------
def test_hop_distance_noncontiguous_layout():
    """Regression: the ring wraparound must be measured on the *device
    ring extent*, not p — a sparse layout over a larger mesh axis used
    to under- (or negatively) count the wrap arm."""
    from repro.core import bpipe as BP
    # 4 stages scattered over an 8-device ring: pair (0,3) sits on
    # devices (0, 7) — adjacent across the wraparound, NOT 7 hops
    plan = BP.plan(4, 8, stage_to_device=(0, 5, 2, 7))
    assert BP.ring_extent(plan) == 8
    d = BP.hop_distance(plan)
    assert d[(0, 3)] == 1                        # wrap: min(7, 8-7)
    assert d[(1, 2)] == 3                        # |5-2| vs 8-3
    # an explicit larger physical ring stretches the wrap arm
    d16 = BP.hop_distance(plan, ring_size=16)
    assert d16[(0, 3)] == 7 and d16[(1, 2)] == 3
    # and every distance is a true ring metric: 0 <= d <= extent // 2
    for (a, b), hops in d.items():
        assert 0 <= hops <= 4, (a, b, hops)


# ---------------------------------------------------------------------------
# _remote_remat: gradient parity vs the non-remat stage fn
# ---------------------------------------------------------------------------
REMAT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, %r)
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.pipeline.spmd import _bpipe_perms, _remote_remat

    p = 4
    mesh = jax.make_mesh((p,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    perm_out, perm_back = _bpipe_perms(p)

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"]) + params["b"]

    remat_fn = _remote_remat(stage_fn, perm_out, perm_back, "model")

    k = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(k, (8, 8), jnp.float32) * 0.3,
              "b": jnp.float32(0.1)}
    x = jax.random.normal(jax.random.fold_in(k, 1), (p, 2, 8), jnp.float32)

    def loss(fn):
        def inner(params, x):
            y = fn(params, x)
            return jax.lax.psum(jnp.sum(y * y), "model")
        def outer(params, x):
            f = jax.shard_map(inner, mesh=mesh,
                              in_specs=(P(), P("model")), out_specs=P())
            return f(params, x)
        return outer

    g_plain = jax.jit(jax.grad(loss(stage_fn)))(params, x)
    g_remat = jax.jit(jax.grad(loss(remat_fn)))(params, x)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 1e-6, err

    # the remote stash is real: the remat grad lowers extra
    # collective-permutes (the EVICT out and the LOAD back)
    txt_p = jax.jit(jax.grad(loss(stage_fn))).lower(params, x) \\
        .compile().as_text()
    txt_r = jax.jit(jax.grad(loss(remat_fn))).lower(params, x) \\
        .compile().as_text()
    assert txt_r.count("collective-permute") > txt_p.count(
        "collective-permute"), (txt_r.count("collective-permute"),
                                txt_p.count("collective-permute"))
    print("REMAT_OK")
""") % SRC


@pytest.mark.slow
def test_remote_remat_grad_parity_subprocess():
    r = subprocess.run([sys.executable, "-c", REMAT_SCRIPT],
                       capture_output=True, text=True, timeout=600)
    assert "REMAT_OK" in r.stdout, r.stdout + r.stderr
