"""Per-arch smoke tests (reduced configs) + MoE dispatch correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import INPUT_SHAPES
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import Model
from repro.models.moe import init_moe, moe_ffn, moe_ffn_dense_oracle

B, S = 2, 16


def _batch_for(cfg, rng, seq=S, batch=B):
    if cfg.frontend == "vision":
        return {"embeds": jax.random.normal(rng, (batch, seq, cfg.d_model),
                                            dtype=jnp.float32)}
    b = {"tokens": jax.random.randint(rng, (batch, seq), 0, cfg.vocab)}
    if cfg.is_encoder_decoder:
        b["enc_embeds"] = jax.random.normal(
            jax.random.fold_in(rng, 1), (batch, cfg.encoder_seq_len,
                                         cfg.d_model), dtype=jnp.float32)
    return b


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_smoke_forward_and_train_step(arch):
    """Reduced variant (≤2-4 layers, d_model ≤ 512, ≤4 experts): one forward
    + one train step; asserts shapes and finiteness."""
    cfg = get_config(arch).reduced(
        n_layers=4 if arch == "jamba-1.5-large-398b" else 2)
    assert cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, jax.random.PRNGKey(1))
    logits, aux = model.forward(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    # one training step (grad + loss finite)
    if "tokens" in batch:
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch, remat=True))(params)
        assert bool(jnp.isfinite(loss))
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads)))
        assert bool(jnp.isfinite(gn))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_matches_forward(arch):
    """prefill + serve_step ≡ full forward — the KV-cache correctness test."""
    cfg = get_config(arch).reduced(
        n_layers=4 if arch == "jamba-1.5-large-398b" else 2)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    K = 3
    rng = jax.random.PRNGKey(1)
    batch = _batch_for(cfg, rng, seq=S + K)
    full_logits, _ = model.forward(params, batch, capacity_factor=100.0)

    cache = model.init_cache(B, S + K)
    pre = dict(batch)
    if "tokens" in pre:
        pre["tokens"] = batch["tokens"][:, :S]
    else:
        pre["embeds"] = batch["embeds"][:, :S]
    lg, cache, _ = model.prefill(params, pre, cache)
    tol = 2e-4 * cfg.vocab ** 0.0 + 5e-4
    assert float(jnp.abs(lg - full_logits[:, S - 1]).max()) < tol
    for i in range(K):
        nxt = (batch["tokens"][:, S + i] if "tokens" in batch
               else batch["embeds"][:, S + i : S + i + 1])
        lg, cache, _ = model.serve_step(params, cache, nxt)
        assert float(jnp.abs(lg - full_logits[:, S + i]).max()) < tol


def test_moe_dispatch_matches_dense_oracle():
    cfg = get_config("qwen3-moe-235b-a22b").reduced()
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, aux = moe_ffn(p, cfg, x, capacity_factor=100.0)
    y_ref = moe_ffn_dense_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5, rtol=1e-4)
    # counts: every token contributes exactly top_k assignments
    assert int(aux["counts"].sum()) == 2 * 16 * cfg.moe.top_k


def test_moe_capacity_drops_tokens():
    cfg = get_config("qwen3-moe-235b-a22b").reduced()
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))
    y_lo, _ = moe_ffn(p, cfg, x, capacity_factor=0.25)
    y_hi, _ = moe_ffn(p, cfg, x, capacity_factor=100.0)
    # drops must change the output (and not NaN)
    assert bool(jnp.isfinite(y_lo).all())
    assert float(jnp.abs(y_lo - y_hi).max()) > 0


def test_moe_counts_are_eam_rows():
    """aux counts == per-sequence routed-token histogram (the EAM rows)."""
    cfg = get_config("qwen3-moe-235b-a22b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (3, 8), 0,
                                          cfg.vocab)}
    _, aux = model.forward(params, batch)
    counts = np.asarray(aux["counts"])   # (n_moe_layers, B, E)
    assert counts.shape == (len(model.moe_layers), 3, cfg.moe.n_experts)
    k = cfg.moe.top_k
    np.testing.assert_array_equal(counts.sum(axis=-1), 8 * k)


def test_gemma_sliding_window_masks_history():
    cfg = get_config("gemma2-2b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S_long = 160  # > reduced window of 128
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, S_long), 0, cfg.vocab)
    logits, _ = model.forward(params, {"tokens": toks})
    # perturb a token far outside every local window; with alternating
    # local/global the *global* layers still see it, so just assert finite +
    # shape here and rely on decode equivalence for exactness
    assert bool(jnp.isfinite(logits).all())


def test_long_decode_windowed_cache():
    """gemma2 long-context variant: ring-buffer cache == full cache while
    within the window."""
    cfg = get_config("gemma2-2b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    win = cfg.attn.sliding_window
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, cfg.vocab)
    full_cache = model.init_cache(1, 64)
    lg_a, full_cache, _ = model.prefill(params, {"tokens": toks[:, :16]},
                                        full_cache)
    ring_cache = model.init_cache(1, 64, decode_window=win)
    lg_b, ring_cache, _ = model.prefill(params, {"tokens": toks[:, :16]},
                                        ring_cache)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b), atol=1e-4)
    for i in range(4):
        lg_a, full_cache, _ = model.serve_step(params, full_cache,
                                               toks[:, 16 + i])
        lg_b, ring_cache, _ = model.serve_step(params, ring_cache,
                                               toks[:, 16 + i],
                                               decode_window=win)
        np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                                   atol=2e-4)


def test_blocked_attention_matches_naive():
    """Flash-style blocked attention (the §Perf lever) ≡ naive scores,
    including GQA, sliding windows and logit softcaps (gemma2)."""
    import dataclasses
    for arch in ("qwen2-1.5b", "gemma2-2b"):
        cfg = get_config(arch).reduced()
        m1 = Model(cfg)
        m2 = Model(dataclasses.replace(cfg, attn_impl="blocked"))
        params = m1.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 160), 0,
                                  cfg.vocab)
        l1, _ = m1.forward(params, {"tokens": toks})
        l2, _ = m2.forward(params, {"tokens": toks})
        assert float(jnp.abs(l1 - l2).max()) < 2e-4


def test_grouped_moe_dispatch_matches_oracle():
    """GShard-style grouped dispatch (§Perf lever) ≡ dense-mask oracle."""
    import dataclasses
    cfg = get_config("qwen3-moe-235b-a22b").reduced()
    cfg_g = dataclasses.replace(cfg, moe_dispatch="grouped")
    from repro.models.moe import init_moe, moe_ffn, moe_ffn_dense_oracle
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    y, aux = moe_ffn(p, cfg_g, x, capacity_factor=100.0)
    y_ref = moe_ffn_dense_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5, rtol=1e-4)
    assert int(aux["counts"].sum()) == 4 * 16 * cfg.moe.top_k


# ---------------------------------------------------------------------------
# Routed-row MoE path (decode: dropless, T·k < E)
# ---------------------------------------------------------------------------

def _moe_cfg(top_k=2, act="swiglu", n_shared=0, n_experts=None):
    import dataclasses
    cfg = get_config("qwen3-moe-235b-a22b").reduced()
    m = dataclasses.replace(cfg.moe, top_k=top_k, n_shared_experts=n_shared,
                            n_experts=n_experts or cfg.moe.n_experts)
    return dataclasses.replace(cfg, act=act, moe=m)


def _dispatches(cfg, x, p, **kw):
    """Whether moe_ffn's traced program runs the capacity dispatch (its
    argsort) rather than the routed rows."""
    from repro.models.moe import moe_ffn
    jaxpr = jax.make_jaxpr(lambda p, x: moe_ffn(p, cfg, x, **kw))(p, x)
    return " sort[" in str(jaxpr)


@pytest.mark.parametrize("n_shared", [0, 1], ids=["routed", "shared"])
@pytest.mark.parametrize("act", ["swiglu", "gelu"])
@pytest.mark.parametrize("top_k", [1, 2])
def test_routed_rows_match_dense_oracle(top_k, act, n_shared):
    """At batch one the decode capacity takes the routed-row path, which
    matches the dense-mask oracle, gated or plain, with or without a
    shared expert."""
    from repro.models.moe import route, routed_rows
    cfg = _moe_cfg(top_k, act, n_shared)
    E = cfg.moe.n_experts
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert ("w_gate" in p) == (act == "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1, cfg.d_model))
    cf = E / top_k
    assert routed_rows(cfg, 1, 1, cf)
    assert not _dispatches(cfg, x, p, capacity_factor=cf)
    y, aux = moe_ffn(p, cfg, x, capacity_factor=cf)
    y_ref = moe_ffn_dense_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5, rtol=1e-4)
    _, idx, _ = route(p, cfg.moe, x.reshape(1, -1))
    want = np.zeros((1, E), np.int32)
    want[0, np.asarray(idx)[0]] = 1
    np.testing.assert_array_equal(np.asarray(aux["counts"]), want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_routed_rows_match_dispatch_at_top1(dtype):
    """Top-1 at batch one: the routed rows and the capacity dispatch (the
    same expert FFN through the ``expert_fn`` seam, which keeps the
    dispatch) pick the same expert and gate: identical counts and aux
    loss, and outputs equal up to the GEMM's summation order. Not bit for
    bit on the CPU: XLA emits the one-row GEMM as a fused vector-matrix
    loop and the E-row one as a batched library dot (float32 differs in
    the last bits, ~1e-6 by norm; bfloat16 mostly rounds them away)."""
    from repro.models.moe import grouped_expert_ffn
    cfg = _moe_cfg(top_k=1)
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.dtype(dtype))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, cfg.d_model)
                          ).astype(dtype)
    cf = float(cfg.moe.n_experts)
    y, aux = moe_ffn(p, cfg, x, capacity_factor=cf)
    y_d, aux_d = moe_ffn(p, cfg, x, capacity_factor=cf,
                         expert_fn=lambda xg, q: grouped_expert_ffn(
                             xg, q, cfg.act))
    assert y.dtype == y_d.dtype == jnp.dtype(dtype)
    assert np.array_equal(np.asarray(aux["counts"]),
                          np.asarray(aux_d["counts"]))
    assert float(aux["aux_loss"]) == float(aux_d["aux_loss"])
    a, b = (np.asarray(v, np.float64) for v in (y, y_d))
    if dtype == "float32":
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    else:
        # below half a bfloat16 ulp at unit scale, by norm
        assert np.linalg.norm(a - b) <= 2 ** -9 * np.linalg.norm(b)


@pytest.mark.parametrize("case,B,S,kw,routed", [
    ("decode", 1, 1, {"capacity_factor": 128.0}, True),
    ("decode_pool", 8, 1, {"capacity_factor": 128.0}, True),
    ("prefill_mask", 1, 16, {"capacity_factor": 2.0, "masked": True}, False),
    ("lossy_factor", 8, 1, {"capacity_factor": 1.25}, False),
    ("tk_at_least_e", 128, 1, {"capacity_factor": 128.0}, False),
    ("expert_fn", 1, 1, {"capacity_factor": 128.0, "custom_experts": True},
     False),
])
def test_routed_rows_predicate(case, B, S, kw, routed):
    """The routed-row path needs a dropless capacity, T·k < E, no token
    mask and no expert_fn; prefill, a lossy factor, wide batches and the
    sharded seam keep the dispatch. ``expert_rows`` labels it, and the
    traced program takes the path the predicate names."""
    from repro.models.moe import expert_rows, grouped_expert_ffn, routed_rows
    cfg = _moe_cfg(top_k=1, n_experts=128)
    assert routed_rows(cfg, B, S, **kw) is routed
    assert expert_rows(cfg, B, S, **kw) == (B * S if routed else 128)
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.zeros((B, S, cfg.d_model))
    call = {"capacity_factor": kw["capacity_factor"]}
    if kw.get("masked"):
        call["token_mask"] = jnp.ones((B, S), bool)
    if kw.get("custom_experts"):
        call["expert_fn"] = lambda xg, q: grouped_expert_ffn(xg, q, cfg.act)
    assert _dispatches(cfg, x, p, **call) is not routed
