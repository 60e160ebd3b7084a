"""The DeepSeek-V3 block (Moonlight-16B-A3B) against the benchmark's plain
reference ``bench/models/deepseek_v3.py``, at tiny sizes on the CPU:
MLA (reference attention and the flash kernel in interpret mode), the
flash kernel with a value head narrower than q/k, sigmoid routing with a
selection bias, dropless routing over the experts a layer holds, the
expert-parallel share, the leading dense layer through the stage split,
the parameter / FLOP / memory counts, and a tiny cell through the
benchmark harness."""
import copy
import dataclasses
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, reference  # noqa: E402
from bench.models import deepseek_v3 as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import flops as program_flops  # noqa: E402
from repro.core import memory_model as MM  # noqa: E402
from repro.core.notation import from_model  # noqa: E402
from repro.core.plan import ScheduleSpec  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import attention as attn_mod  # noqa: E402
from repro.models import mla as mla_mod  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.layers import apply_mlp  # noqa: E402
from repro.pipeline import PipelineExecutor  # noqa: E402
from repro.pipeline import stage as stage_mod  # noqa: E402

KEY = jax.random.PRNGKey(17)
CELL = "moonlight-16b-a3b-ep8.bpipe-flash-s2048"
#: A tiny DeepSeek-V3 model block: one dense layer, two MoE layers of 8
#: experts (top-3), of which experts 2-5 are held, 2 shared experts.
TINY = {"num_layers": 3, "first_k_dense": 1, "d_model": 64, "num_heads": 2,
        "num_kv_heads": 2, "head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "kv_lora_rank": 32, "d_ff": 128, "vocab_size": 256,
        "moe": {"num_experts": 8, "top_k": 3, "d_ff": 32, "shared_experts": 2,
                "scoring": "sigmoid", "routed_scale": 2.446, "dropless": True,
                "held": 4, "first_held": 2}}
#: Limits of the tiny cell from ``bench/control.py``'s readings on the
#: CPU (seeds 2**33 + 100 to 111): the program read at most loss_gap
#: 1.84e-3, grad_gap 2.66e-2 and update_gap 1.02e-2; the float8 control
#: at least 2.57e-3, 3.32e-2 and 1.32e-2; with the routed scale left out
#: (seed 2**33 + 7) 8.2e-3, 0.52 and 2.7e-2.
TINY_LIMITS = {"loss_gap": 2.2e-3, "grad_gap": 3e-2, "update_gap": 1.2e-2,
               "window_compiles": 0}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _tiny_cfg(**moe_kw):
    """The registered config at the tiny sizes, in float32."""
    cell = harness.find_cell(CELL)
    config = copy.deepcopy(cell.config)
    config["model"].update(copy.deepcopy(TINY))
    config["model"]["moe"].update(moe_kw)
    cfg = harness.model_config(config, {"attn_impl": "reference"})
    return dataclasses.replace(cfg, dtype="float32"), config["model"]


def _allclose(a, b, tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=tol,
                                   rtol=tol)


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_mla_matches_reference(impl):
    cfg, m = _tiny_cfg()
    p = mla_mod.init_mla(KEY, cfg)
    lp = {"wq": p["wq"], "wkv_a": p["wkv_a"], "wkv_b": p["wkv_b"],
          "wo": p["wo"],
          "kv_scale": 1.0 + 0.1 * jax.random.normal(KEY, (cfg.kv_lora_rank,))}
    p["kv_norm"]["scale"] = lp["kv_scale"]
    b, s = 2, 32
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def prog(p_, x_):
        return mla_mod.mla(p_, x_, cfg, pos, impl=impl)

    def want(lp_, x_):
        return ref.mla(m, None, lp_, x_)

    y, vjp = jax.vjp(prog, p, x)
    y_ref, vjp_ref = jax.vjp(want, lp, x)
    _allclose(y, y_ref, 2e-4)
    g = jax.random.normal(jax.random.fold_in(KEY, 2), y.shape)
    (gp, gx), (glp, gx_ref) = vjp(g), vjp_ref(g)
    _allclose(gx, gx_ref, 2e-4)
    for k in ("wq", "wkv_a", "wkv_b", "wo"):
        _allclose(gp[k], glp[k], 2e-4)
    _allclose(gp["kv_norm"]["scale"], glp["kv_scale"], 2e-4)


def test_mla_rope_uses_the_published_permutation():
    """A rotary vector is permuted out of interleaved order before
    rotate-half: at position 1, dims (0, 1) rotate together, not
    (0, half)."""
    x = jnp.zeros((1, 2, 1, 8)).at[0, 1, 0, 0].set(1.0)
    y = ref.rope(x, 1.0)
    # dim 0 moves to slot 0 of the permuted vector and rotates into slot
    # 4 (its rotate-half partner): cos(1), sin(1)
    np.testing.assert_allclose(np.asarray(y[0, 1, 0, [0, 4]]),
                               [np.cos(1.0), np.sin(1.0)], atol=1e-6)
    np.testing.assert_allclose(np.asarray(mla_mod._deinterleave(
        jnp.arange(8.0))), [0, 2, 4, 6, 1, 3, 5, 7])


def test_flash_value_head_narrower_than_qk():
    ks = jax.random.split(KEY, 4)
    cfg, _ = _tiny_cfg()
    q = jax.random.normal(ks[0], (2, 64, 4, 48))
    k = jax.random.normal(ks[1], (2, 64, 4, 48))
    v = jax.random.normal(ks[2], (2, 64, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, True, 0, 0.0, None, 32, 16, True)

    def sdpa(q, k, v):
        return attn_mod._sdpa(q, k, v, cfg, pos, pos, causal=True, window=0)

    out, vjp = jax.vjp(flash, q, k, v)
    want, vjp_ref = jax.vjp(sdpa, q, k, v)
    assert out.shape == (2, 64, 4, 32)
    _allclose(out, want, 1e-5)
    g = jax.random.normal(ks[3], out.shape)
    _allclose(vjp(g), vjp_ref(g), 1e-5)


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------
def test_sigmoid_selection_bias_changes_choice_not_gates():
    cfg, _ = _tiny_cfg(held=0, first_held=0)
    p = moe_mod.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 32, cfg.d_model))
    scores = jax.nn.sigmoid(x @ p["router"])
    gates, idx, aux = moe_mod.route(p, x, cfg)
    assert float(aux) == 0.0
    # the chosen scores, normalised to sum 1, times the routed scale
    top = jnp.take_along_axis(scores, idx, -1)
    _allclose(gates, top / top.sum(-1, keepdims=True) * 2.446, 1e-6)
    _allclose(gates.sum(-1), jnp.full(idx.shape[:2], 2.446), 1e-5)
    # a bias on expert 7 puts it among every token's picks ...
    biased = dict(p, route_bias=p["route_bias"].at[7].set(10.0))
    g2, idx2, _ = moe_mod.route(biased, x, cfg)
    assert bool(jnp.all(jnp.any(idx2 == 7, -1)))
    assert not bool(jnp.all(jnp.any(idx == 7, -1)))
    # ... while each gate is still the unbiased score of its expert
    top2 = jnp.take_along_axis(scores, idx2, -1)
    _allclose(g2, top2 / top2.sum(-1, keepdims=True) * 2.446, 1e-6)
    # and the bias gets no gradient
    gb = jax.grad(lambda b: moe_mod.route(dict(p, route_bias=b), x, cfg)[0]
                  .sum())(p["route_bias"])
    assert float(jnp.max(jnp.abs(gb))) == 0.0


def _dense_moe(p, x, cfg, lo=0, held=None):
    """Every pick of an expert in [lo, lo + held), computed token by
    token from ``p``'s experts (all of them): sum of gate * SwiGLU expert
    (no shared experts)."""
    e = cfg.moe
    held = held or e.num_experts
    gates, idx, _ = moe_mod.route(p, x, cfg)
    y = jnp.zeros_like(x)
    for j in range(e.top_k):
        ex = idx[..., j]
        mine = (ex >= lo) & (ex < lo + held)
        w = {k: p[k][ex] for k in ("wi", "wg", "wo")}
        h = jax.nn.silu(jnp.einsum("bsd,bsdf->bsf", x, w["wi"])) \
            * jnp.einsum("bsd,bsdf->bsf", x, w["wg"])
        out = jnp.einsum("bsf,bsfd->bsd", h, w["wo"])
        y = y + jnp.where(mine[..., None], out * gates[..., j:j + 1], 0.0)
    return y


def test_dropless_keeps_every_pick_where_capacity_drops():
    """Every token's choice includes expert 0 (a large selection bias):
    the dropless path matches the token-by-token reference; the capacity
    path (factor 1) drops most of expert 0's picks."""
    cfg, _ = _tiny_cfg(held=0, first_held=0, shared_experts=0)
    p = moe_mod.init_moe(KEY, cfg)
    p["route_bias"] = p["route_bias"].at[0].set(10.0)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 32, cfg.d_model))
    want = _dense_moe(p, x, cfg)
    y, _ = moe_mod.apply_moe(p, x, cfg)
    _allclose(y, want, 1e-4)
    capped = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, dropless=False, capacity_factor=1.0))
    y_cap, _ = moe_mod.apply_moe(p, x, capped)
    assert float(jnp.max(jnp.abs(y_cap - want))) > 1e-2


def test_expert_shares_sum_to_the_uncut_layer():
    """Guide §4: 8 shares of 16 experts (2 held each), the shared experts
    counted once, add up to the layer that holds all 16."""
    cfg, _ = _tiny_cfg(num_experts=16, held=0, first_held=0)
    p = moe_mod.init_moe(KEY, cfg)
    p["route_bias"] = 0.1 * jax.random.normal(KEY, (16,))
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 32, cfg.d_model))
    full, _ = moe_mod.apply_moe(p, x, cfg)
    shared = apply_mlp(p["shared"], x, cfg)
    total = shared
    for i in range(8):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=2, first_held=2 * i))
        sp = {k: v for k, v in p.items() if k != "shared"}
        sp.update({k: p[k][2 * i:2 * i + 2] for k in ("wi", "wg", "wo")})
        y, _ = moe_mod.apply_moe(sp, x, share)
        _allclose(y, _dense_moe(p, x, cfg, 2 * i, 2), 1e-4)
        total = total + y
    _allclose(total, full, 1e-4)


@pytest.mark.parametrize("bias", [100.0, -100.0, 0.0])
def test_routing_counter_counts_held_picks(bias):
    """The reference's counter over 2 MoE layers x 2 rows x 16 tokens x
    top-3: a selection bias of +-100 on the held experts 2-5 sends every
    pick to them or none; with no bias the counts are consistent."""
    _, m = _tiny_cfg()
    t = reference.init_params(ref, m, KEY)
    t["layers"]["moe"]["route_bias"] = jnp.zeros((2, 8)).at[:, 2:6].set(bias)
    tokens = jax.random.randint(KEY, (2, 16), 0, m["vocab_size"])
    c = jax.device_get(ref.held_load(m, t, tokens))
    assert int(c["picks"]) == 2 * 2 * 16 * 3
    held = int(c["held_picks"])
    assert float(c["mean_load"]) == pytest.approx(held / (2 * 2 * 4))
    if bias > 0:
        assert held == 192 and int(c["max_load"]) == 16
    elif bias < 0:
        assert held == 0 and int(c["max_load"]) == 0
    else:
        assert 0 < held < 192
        assert held / 16 <= int(c["max_load"]) <= 16


# ---------------------------------------------------------------------------
# Leading dense layer through the stages
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("p", [2, 4])
def test_split_merge_round_trip_with_leading_dense_layer(p):
    cfg, _ = _tiny_cfg()
    cfg = dataclasses.replace(cfg, num_layers=5)
    params = M.init_params(KEY, cfg)
    assert set(params["blocks"]) == {"lead0", "pos0"}
    assert "router" not in params["blocks"]["lead0"]["ffn"]
    sp = stage_mod.StageSplitter(cfg, p)
    stages = sp.split(params)
    assert "router" not in stages[0]["layers"][0]["ffn"]
    merged = sp.merge(stages)
    _allclose(merged["blocks"], params["blocks"], 0.0)
    assert jax.tree.structure(merged) == jax.tree.structure(params)


@pytest.mark.parametrize("p", [2, 4])
def test_executor_matches_loss_fn_with_leading_dense_layer(p):
    cfg, _ = _tiny_cfg()
    cfg = dataclasses.replace(cfg, num_layers=5, attn_impl="flash")
    params = M.init_params(KEY, cfg)
    params["blocks"]["pos0"]["ffn"]["route_bias"] = 0.05 * jax.random.normal(
        KEY, params["blocks"]["pos0"]["ffn"]["route_bias"].shape)
    toks = jax.random.randint(jax.random.fold_in(KEY, 6), (4, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    loss, grads = jax.value_and_grad(
        lambda q: M.loss_fn(q, batch, cfg)[0])(params)
    res = PipelineExecutor(cfg, spec=ScheduleSpec("bpipe", p, 4),
                           micro_batch=1, remat="flash").step(params, batch)
    assert abs(float(res.loss) - float(loss)) < 1e-5
    _allclose(res.grads, grads, 2e-4)


# ---------------------------------------------------------------------------
# Counts against the reference's shapes
# ---------------------------------------------------------------------------
def _cell_cfg():
    cell = harness.find_cell(CELL)
    return harness.model_config(cell.config, cell.job), cell.config["model"]


def test_param_count_matches_reference_shapes():
    cfg, m = _cell_cfg()
    shapes = jax.tree.leaves(ref.param_shapes(m),
                             is_leaf=lambda x: isinstance(x, tuple))
    n = sum(int(np.prod(s)) for s in shapes)
    # param_count leaves out the final norm's scale
    assert cfg.param_count() == n - cfg.d_model
    assert 568e6 < n < 569e6
    # the memory model charges the blocks' share and the vocabulary apart
    nt = from_model(cfg, b=1, s=2048, B=8, p=4, t=1)
    blocks = MM.param_bytes_per_stage(nt, cfg) * 4 / MM.BYTES_PER_PARAM
    assert blocks + MM.vocab_param_count(nt, cfg) == pytest.approx(
        n - cfg.d_model)
    # the program's own layout holds the same parameters
    params = jax.eval_shape(lambda k: M.init_params(k, cfg), KEY)
    assert sum(a.size for a in jax.tree.leaves(params)) == n


@pytest.mark.parametrize("b,s", [(1, 2048), (8, 2048), (8, 512)])
def test_flops_match_reference(b, s):
    cfg, m = _cell_cfg()
    assert program_flops.model_flops_train(cfg, b, s) == pytest.approx(
        ref.model_flops_train(m, b, s), rel=1e-12)


def test_planner_prices_the_config():
    from bench.entries import executor
    cell = harness.find_cell(CELL)
    cfg = harness.model_config(cell.config, cell.job)
    entry = executor.Entry.__new__(executor.Entry)
    sch = cell.job["schedule"]
    entry.cfg, entry.job = cfg, cell.job
    entry.spec = ScheduleSpec(sch["kind"], sch["p"], sch["m"])
    assert entry.planner_step_s(197e12) > 0


def test_work_counts():
    _, m = _cell_cfg()
    f, nbytes = ref.attention_work(m, 1, 2048)
    assert f == 3 * 2 * 16 * 2048 * 2048 * (192 + 128) / 2
    assert nbytes == (6 * 2048 * 16 * 192 + 6 * 2048 * 16 * 128) * 2 \
        + 2 * 16 * 2048 * 4
    f, nbytes = ref.expert_work(m, 1, 2048)
    rows = 2048 * 6 * 8 / 64                           # 192 per held expert
    assert f == 18 * rows * 2048 * 1408
    assert nbytes == 3 * (3 * rows * (2048 + 1408) + 3 * 8 * 2048 * 1408) * 2
    f, nbytes = ref.expert_work(m, 1, 2048, rows=1000)
    assert f == 18 * 1000 * 2048 * 1408
    assert nbytes == 3 * (3 * 1000 * (2048 + 1408) + 3 * 8 * 2048 * 1408) * 2


# ---------------------------------------------------------------------------
# A tiny cell through the harness
# ---------------------------------------------------------------------------
def _tiny_cell():
    cell = harness.find_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(copy.deepcopy(TINY))
    cell.job = {**copy.deepcopy(cell.job), "rows": 4, "seq": 64,
                "schedule": {"kind": "bpipe", "p": 2, "m": 4}}
    cell.limits = dict(TINY_LIMITS)
    cell.per_layer = [{"name": "mfu", "unit": "%"}]
    return cell


def _run(traced, seed=2**33 + 41):
    return harness.run(_tiny_cell(), seed, 0.5, traced,
                       t0=time.perf_counter(), devices=jax.devices()[:1],
                       log=lambda s: None, peaks=PEAKS)


def test_tiny_cell_is_correct():
    r = _run(traced=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["checks"]["window_compiles"]["value"] == 0
    assert r["metrics"]["mfu"]["value"] > 0


def test_tiny_cell_without_routed_scale_is_not_correct(monkeypatch):
    route = moe_mod.route

    def unscaled(p, x, cfg):
        return route(p, x, dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, routed_scale=1.0)))

    monkeypatch.setattr(moe_mod, "route", unscaled)
    r = _run(traced=False)
    assert r["correct"] is False, r["checks"]


def test_reference_bias_gets_no_gradient():
    _, m = _tiny_cfg()
    params = reference.init_params(ref, m, KEY)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(KEY, (1, 16, m["d_model"]))
    g = jax.grad(lambda lp_: ref.moe(m, None, lp_, x).sum())(lp)
    assert float(jnp.max(jnp.abs(g["route_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0
    assert "route_bias" not in ref.DECAYED and "kv_scale" not in ref.DECAYED
