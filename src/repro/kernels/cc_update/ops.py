"""Jit'd high-level wrapper: CCState/CCEvent pytrees -> cc_update kernel.

Drop-in replacement for ``repro.core.smartt.smartt_update`` (SMaRTT fields
only) running through the Pallas kernel.  ``interpret`` comes from
``repro.kernels.interpret_mode`` (the interpreter off-TPU, Mosaic on it).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.types import CCEvent, CCParams, CCState
from repro.kernels.cc_update import ref as R
from repro.kernels.cc_update.kernel import cc_update


def pack_params(p: CCParams) -> jnp.ndarray:
    return jnp.stack([jnp.asarray(getattr(p, n), jnp.float32).reshape(())
                      for n in R.PARAM_FIELDS])


def smartt_update_pallas(p: CCParams, s: CCState, ev: CCEvent, now,
                         *, interpret: bool) -> CCState:
    F = s.cwnd.shape[0]
    brtt = jnp.broadcast_to(p.brtt, (F,)).astype(jnp.float32)
    trtt = jnp.broadcast_to(p.trtt, (F,)).astype(jnp.float32)
    mi = jnp.broadcast_to(p.mi, (F,)).astype(jnp.float32)
    sf = tuple(getattr(s, n).astype(jnp.float32) for n in R.STATE_F32)
    si = (s.trigger_qa.astype(jnp.int32), s.fi_active.astype(jnp.int32),
          s.ack_count.astype(jnp.int32))
    ef = tuple(getattr(ev, n).astype(jnp.float32) for n in R.EVENT_F32)
    ei = tuple(getattr(ev, n).astype(jnp.int32) for n in R.EVENT_I32)
    f32s, i32s = cc_update(pack_params(p), now, brtt, trtt, mi,
                           sf, si, ef, ei, interpret=interpret)
    kw = dict(zip(R.STATE_F32, f32s))
    kw["trigger_qa"] = i32s[0] != 0
    kw["fi_active"] = i32s[1] != 0
    kw["ack_count"] = i32s[2]
    return s._replace(**kw)
