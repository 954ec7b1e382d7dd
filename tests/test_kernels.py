"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
sweeping shapes and dtypes."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.smartt import smartt_update
from repro.core.types import CCEvent, init_cc_state, make_cc_params
from repro.kernels import interpret_mode
from repro.kernels.cc_update.ops import smartt_update_pallas
from repro.kernels.flash_attn.ops import gqa_flash_attention
from repro.kernels.flash_attn.ref import attention_ref
from repro.kernels.red_mark.kernel import red_mark
from repro.kernels.red_mark.ref import red_mark_ref
from repro.kernels.ssd_scan.ops import ssd, ssd_jnp
from repro.kernels.ssd_scan.ref import ssd_ref


# ------------------------------ cc_update ------------------------------


def _random_cc(F, seed):
    rng = np.random.default_rng(seed)
    brtt = np.where(rng.random(F) < 0.5, 26.0, 20.0).astype(np.float32)
    p = make_cc_params(mtu=4096.0, bdp=26 * 4096.0, brtt=brtt)
    s = init_cc_state(F, p)
    s = s._replace(
        cwnd=jnp.asarray(rng.uniform(4096, 133120, F), jnp.float32),
        acked=jnp.asarray(rng.uniform(0, 1e5, F), jnp.float32),
        qa_end=jnp.asarray(rng.choice([0.0, 10.0, 50.0], F), jnp.float32),
        trigger_qa=jnp.asarray(rng.random(F) < 0.3),
        bytes_to_ignore=jnp.asarray(rng.uniform(0, 5e4, F), jnp.float32),
        bytes_ignored=jnp.asarray(rng.uniform(0, 5e4, F), jnp.float32),
        fi_count=jnp.asarray(rng.uniform(0, 2e5, F), jnp.float32),
        fi_active=jnp.asarray(rng.random(F) < 0.2),
        avg_wtd=jnp.asarray(rng.uniform(0, 1, F), jnp.float32),
        ack_count=jnp.asarray(rng.integers(0, 100, F), jnp.int32))
    ev = CCEvent(
        has_ack=jnp.asarray(rng.random(F) < 0.7),
        ack_bytes=jnp.full((F,), 4096.0, jnp.float32),
        ecn=jnp.asarray(rng.random(F) < 0.4),
        rtt=jnp.asarray(rng.uniform(20, 80, F), jnp.float32),
        ack_entropy=jnp.zeros((F,), jnp.int32),
        n_trims=jnp.asarray(rng.integers(0, 3, F), jnp.int32),
        trim_bytes=jnp.asarray(rng.integers(0, 3, F) * 4096.0, jnp.float32),
        n_timeouts=jnp.asarray(rng.integers(0, 2, F), jnp.int32),
        to_bytes=jnp.asarray(rng.integers(0, 2, F) * 4096.0, jnp.float32),
        unacked=jnp.asarray(rng.uniform(0, 1e5, F), jnp.float32),
        credit_grant=jnp.zeros((F,), jnp.float32))
    return p, s, ev


@pytest.mark.parametrize("F", [1, 7, 128, 1000])
def test_cc_update_kernel_matches_oracle(F):
    p, s, ev = _random_cc(F, F)
    ref = smartt_update(p, s, ev, 42.0)
    out = smartt_update_pallas(p, s, ev, 42.0, interpret=interpret_mode())
    for name in ("cwnd", "acked", "qa_end", "trigger_qa", "bytes_to_ignore",
                 "bytes_ignored", "fi_count", "fi_active", "avg_wtd",
                 "ack_count"):
        np.testing.assert_allclose(
            np.asarray(getattr(ref, name), np.float32),
            np.asarray(getattr(out, name), np.float32),
            rtol=1e-6, atol=1e-3, err_msg=f"F={F} field={name}")


# ------------------------------ red_mark ------------------------------


@pytest.mark.parametrize("Q", [5, 130, 1024])
@pytest.mark.parametrize("tick", [0, 17, 65535])
def test_red_mark_matches_oracle(Q, tick):
    rng = np.random.default_rng(Q + tick)
    qs = jnp.asarray(rng.integers(0, 27, Q), jnp.int32)
    ar = jnp.asarray(rng.integers(0, 6, Q), jnp.int32)
    got = red_mark(qs, ar, 26, 5.2, 20.8, tick, 0xECD,
                   interpret=interpret_mode())
    want = red_mark_ref(qs, ar, jnp.int32(26), jnp.float32(5.2),
                        jnp.float32(20.8), jnp.int32(tick), jnp.int32(0xECD))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_red_mark_probability_is_red_shaped():
    """Marking frequency rises ~linearly between kmin and kmax."""
    Q = 4096
    for q, lo, hi in ((4, 0.0, 0.01), (13, 0.4, 0.6), (25, 0.99, 1.01)):
        qs = jnp.full((Q,), q, jnp.int32)
        mark, _, _ = red_mark(qs, jnp.zeros((Q,), jnp.int32),
                              26, 5.2, 20.8, 3, 0xECD,
                              interpret=interpret_mode())
        frac = float(jnp.mean(mark.astype(jnp.float32)))
        assert lo <= frac <= hi, (q, frac)


# ------------------------------ flash_attn ------------------------------


@pytest.mark.parametrize("case", [
    (1, 2, 2, 128, 128, 64, True, 0, jnp.float32),
    (2, 4, 2, 256, 256, 32, True, 0, jnp.float32),
    (1, 2, 1, 128, 256, 64, True, 0, jnp.float32),
    (1, 2, 2, 128, 128, 64, True, 64, jnp.float32),
    (1, 2, 2, 64, 64, 16, False, 0, jnp.float32),
    (1, 2, 2, 128, 128, 64, True, 0, jnp.bfloat16),
])
def test_flash_attention_matches_oracle(case):
    b, hq, hkv, sq, sk, d, causal, win, dt = case
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), dt)
    k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dt)
    v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dt)
    out = gqa_flash_attention(q, k, v, causal=causal, window=win)
    kr = jnp.repeat(k, hq // hkv, axis=1)
    vr = jnp.repeat(v, hq // hkv, axis=1)
    ref = attention_ref(q, kr, vr, causal=causal, window=win)
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------ ssd_scan ------------------------------


@pytest.mark.parametrize("case", [(2, 64, 16, 32, 16), (1, 128, 64, 128, 32),
                                  (3, 96, 8, 16, 48)])
def test_ssd_kernel_and_jnp_match_sequential(case):
    BH, L, P, N, chunk = case
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((BH, L, P)) * 0.5, jnp.float32)
    loga = jnp.asarray(-np.abs(rng.standard_normal((BH, L))) * 0.3, jnp.float32)
    B = jnp.asarray(rng.standard_normal((BH, L, N)) * 0.3, jnp.float32)
    C = jnp.asarray(rng.standard_normal((BH, L, N)) * 0.3, jnp.float32)
    ref = ssd_ref(x, loga, B, C)
    np.testing.assert_allclose(np.asarray(ssd(x, loga, B, C, chunk=chunk)),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ssd_jnp(x, loga, B, C, chunk=chunk)),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)
