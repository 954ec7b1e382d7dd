"""Backend dispatch for the packed sent-ring drain kernel.

``get(backend)`` resolves ``SimConfig.transport_backend`` to the drain
callable ``transport.control`` folds its ACK/trim/timeout events through:

  ``drain(t, rto, started, has_ack, ack_seq, lbits, bitmap,
          sent0, sent1, sent2) -> (state', n_to, spur, unacked_pkts)``

with the contract of ``ref.ring_drain_ref`` (unpadded inputs).  Both
backends are bit-for-bit interchangeable (asserted engine-deep in
tests/test_engine_pallas.py); ``pallas`` compiles through Mosaic on a
TPU and runs in interpret mode elsewhere (``kernels.interpret_mode``).
"""

from __future__ import annotations

import functools

from repro.kernels import interpret_mode
from repro.kernels.ring_drain import kernel as K
from repro.kernels.ring_drain import ref as R

BACKENDS = ("jnp", "pallas")


def ring_drain(t, rto, started, has_ack, ack_seq, lbits, bitmap,
               sent0, sent1, sent2, *, backend: str, interpret: bool):
    w = sent0.shape[1]
    ww = lbits.shape[1]
    maxw = bitmap.shape[1]
    if backend == "pallas":
        return K.ring_drain(t, rto, started, has_ack, ack_seq, lbits,
                            bitmap, sent0, sent1, sent2,
                            w=w, ww=ww, maxw=maxw, interpret=interpret)
    return R.ring_drain_ref(t, rto, started, has_ack, ack_seq, lbits,
                            bitmap, sent0, sent1, sent2,
                            w=w, ww=ww, maxw=maxw)


def get(backend: str):
    """Resolve a transport backend name to the drain callable."""
    if backend not in BACKENDS:
        raise KeyError(
            f"unknown transport backend {backend!r}; have {BACKENDS}")
    return functools.partial(ring_drain, backend=backend,
                             interpret=interpret_mode())
