"""Emulated attention on the dispatch seam.

The contract of ``docs/dispatch-seam.md``, verified for the fifth kind:
cross-route bit-identity (the online-softmax scan over the Pallas GEMM
kernels vs over ``emulated_matmul``), FP64-oracle parity, and mode-flipping
end-to-end from the models/ and serve/ layers down to ``dispatch.attention``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch

RNG = np.random.default_rng(11)


def _qkv(S, T, D, lead=()):
    q = jnp.asarray(RNG.standard_normal(lead + (S, D)))
    k = jnp.asarray(RNG.standard_normal(lead + (T, D)))
    v = jnp.asarray(RNG.standard_normal(lead + (T, D)))
    return q, k, v


def _oracle(q, k, v, mask=None, softcap=0.0):
    """Plain materialised-scores softmax attention at FP64."""
    q64, k64, v64 = (np.asarray(x, np.float64) for x in (q, k, v))
    s = q64 @ k64.T / math.sqrt(q.shape[-1])
    if softcap > 0:
        s = softcap * np.tanh(s / softcap)
    if mask is not None:
        s = np.where(np.asarray(mask).astype(bool), s, -1e30)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return p @ v64


# ---------------------------------------------------------------------------
# Cross-route bit-identity (the seam contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["causal", "padded", "decode", "softcap"])
def test_attention_routes_bit_identical(case):
    """xla vs pallas through dispatch.attention — bitwise equal, like every
    other kind on the seam (causal prefill, ragged padded T, decode S=1,
    and the softcapped variant)."""
    if case == "causal":
        q, k, v = _qkv(16, 16, 8)
        mask, softcap = jnp.tril(jnp.ones((16, 16), jnp.int8)), 0.0
    elif case == "padded":
        q, k, v = _qkv(9, 12, 8)        # ragged: pads to bkv internally
        mask = jnp.asarray((np.arange(12) < 10).astype(np.int8))[None, :]
        mask = jnp.broadcast_to(mask, (9, 12))
        softcap = 0.0
    elif case == "decode":
        q, k, v = _qkv(1, 12, 8)
        mask = jnp.asarray((np.arange(12) < 7).astype(np.int8))[None, :]
        softcap = 0.0
    else:
        q, k, v = _qkv(16, 16, 8)
        mask, softcap = jnp.tril(jnp.ones((16, 16), jnp.int8)), 30.0
    y_xla = np.asarray(dispatch.attention(q, k, v, mask=mask,
                                          softcap=softcap, mode="xla"))
    y_pal = np.asarray(dispatch.attention(q, k, v, mask=mask,
                                          softcap=softcap, mode="pallas"))
    np.testing.assert_array_equal(y_xla, y_pal)


def test_attention_batched_leading_dims_both_routes():
    """(..., S, D) leading dims map over independent rows; both routes agree
    with each slice computed alone."""
    q, k, v = _qkv(8, 12, 8, lead=(2, 2))
    mask = jnp.ones((8, 12), jnp.int8)
    y_xla = np.asarray(dispatch.attention(q, k, v, mask=mask, mode="xla"))
    y_pal = np.asarray(dispatch.attention(q, k, v, mask=mask, mode="pallas"))
    assert y_xla.shape == (2, 2, 8, 8)
    np.testing.assert_array_equal(y_xla, y_pal)
    one = np.asarray(dispatch.attention(q[1, 0], k[1, 0], v[1, 0], mask=mask,
                                        mode="xla"))
    np.testing.assert_array_equal(y_xla[1, 0], one)


# ---------------------------------------------------------------------------
# FP64-oracle parity (the emulation claim)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_attention_matches_fp64_oracle(softcap):
    """The xla route (and therefore, by bit-identity, the pallas route)
    matches a plain jnp-free FP64 softmax-attention oracle to well
    under 1e-12 — the QK^T and PV products are exact, only the softmax
    transcendentals differ in evaluation order."""
    q, k, v = _qkv(16, 16, 8)
    mask = jnp.tril(jnp.ones((16, 16), jnp.int8))
    got = np.asarray(dispatch.attention(q, k, v, mask=mask, softcap=softcap,
                                        mode="xla"))
    want = _oracle(q, k, v, mask=mask, softcap=softcap)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_attention_no_mask_means_attend_all():
    q, k, v = _qkv(8, 8, 8)
    got = np.asarray(dispatch.attention(q, k, v, mode="xla"))
    np.testing.assert_allclose(got, _oracle(q, k, v), rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# Mode flipping end-to-end (spy: the routes themselves are intercepted)
# ---------------------------------------------------------------------------

def _spy_attention_routes(monkeypatch):
    """Replace both attention routes with recorders, delegating to the real
    reference so callers still get correct outputs (the interpreted GEMM
    kernels at model shapes would dominate the fast lane otherwise)."""
    from repro.kernels import ozaki_attention

    calls = []
    real_ref = ozaki_attention.attention_ref

    def ref_spy(*a, **kw):
        calls.append("xla")
        return real_ref(*a, **kw)

    def pallas_spy(q, k, v, mask, plan_qk, plan_pv, product, softcap=0.0,
                   bkv=128, out_dtype=jnp.float64):
        calls.append("pallas")
        assert product is dispatch._pallas_matmul
        return real_ref(q, k, v, mask, plan_qk, plan_pv, softcap=softcap,
                        bkv=bkv, out_dtype=out_dtype)

    monkeypatch.setattr(ozaki_attention, "attention_ref", ref_spy)
    monkeypatch.setattr(ozaki_attention, "attention_pallas_gemms", pallas_spy)
    return calls


def test_mode_scope_flips_attention_route(monkeypatch):
    calls = _spy_attention_routes(monkeypatch)
    q, k, v = _qkv(8, 8, 8)
    with dispatch.mode_scope("xla"):
        dispatch.attention(q, k, v)
    with dispatch.mode_scope("pallas"):
        dispatch.attention(q, k, v)
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas")
    dispatch.attention(q, k, v)
    assert calls == ["xla", "pallas", "pallas"]


def test_model_attention_rides_the_seam(monkeypatch):
    """Under an emulated policy the whole model score path goes through
    dispatch.attention — mode_scope flips it like any seam multiplication."""
    from repro.configs import registry
    from repro.models.transformer import Model

    calls = _spy_attention_routes(monkeypatch)
    cfg = registry.get_config("yi-6b", smoke=True, policy_name="ozaki2_int8",
                              compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab_size, (1, 4)).astype(np.int32))}
    with dispatch.mode_scope("xla"):
        logits, _ = model.apply(params, batch)
    assert calls and set(calls) == {"xla"}
    assert bool(jnp.all(jnp.isfinite(logits)))
    calls.clear()
    with dispatch.mode_scope("pallas"):
        model.apply(params, batch)
    assert calls and set(calls) == {"pallas"}


def test_serve_decode_attention_rides_the_seam(monkeypatch):
    """The engine's dispatch_mode pin reaches the attention kind inside
    the jitted decode step (the spy fires at trace time)."""
    from repro.configs import registry
    from repro.models.transformer import Model
    from repro.serve.engine import ServeEngine

    calls = _spy_attention_routes(monkeypatch)
    cfg = registry.get_config("yi-6b", smoke=True, policy_name="ozaki2_int8",
                              compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_slots=1, max_seq=8,
                      dispatch_mode="pallas")
    prompt = RNG.integers(0, cfg.vocab_size, 2).astype(np.int32)
    eng.prefill_slot(0, prompt)
    assert calls and set(calls) == {"pallas"}


def test_model_emulated_matches_fp64_policy():
    """Emulated-policy logits track the fp64-policy model closely: the dense
    layers are FP64-exact by construction and the attention path differs only
    in softmax evaluation precision (f64 emulated vs f32 native)."""
    from repro.configs import registry
    from repro.models.transformer import Model

    batch = None
    outs = {}
    for pol in ("fp64", "ozaki2_int8"):
        cfg = registry.get_config("yi-6b", smoke=True, policy_name=pol,
                                  compute_dtype="float32")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        if batch is None:
            batch = {"tokens": jnp.asarray(
                RNG.integers(0, cfg.vocab_size, (1, 4)).astype(np.int32))}
        with dispatch.mode_scope("xla"):
            outs[pol] = np.asarray(model.apply(params, batch)[0])
    np.testing.assert_allclose(outs["ozaki2_int8"], outs["fp64"],
                               rtol=1e-3, atol=1e-4)
