"""Frozen reference implementation of the pre-superstep engine hot path.

``benchmarks/perf.py`` reports engine throughput as a speedup over "the
K=1 ungated loop" — the engine as it stood before the superstep PR: one
tick per ``while_loop`` iteration, and a scatter-heavy tick (stable-argsort
enqueue ranking, per-emitter ACK scatter with a write-off target, five
separate ACK-drain scatters, three separate trim-ledger scatters, three
separate sent-ring component scatters, scatter-built eligibility/emission
masks).  This module reconstructs that op structure against the current
state containers so the baseline stays measurable after the engine moved
on.  It is benchmark-only code: nothing in the simulator imports it, and
it intentionally does NOT track future engine changes.

The reconstruction produces the same simulated trajectory as the
production step — same fct/goodput/cwnd/tick count (the argsort ranks
equal the production ranks; everything else is op structure, not
semantics) — so ticks/sec comparisons are apples to apples.  One state
leaf intentionally diverges for sender-based algorithms: the seed engine
maintained the EQDS-only ``trim_seen`` ledger unconditionally, so this
baseline does too, while the production step gates it on
``Dims.credit_based``; that cost difference is part of what the speedup
measures.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import registry, reps
from repro.core.types import CCEvent
from repro.kernels.enqueue_arb import ops as enqueue_arb_ops
from repro.netsim import engine, fabric, faults, metrics, sender
from repro.netsim.metrics import HIST_BINS
from repro.netsim.state import pkt_size

I32 = jnp.int32
F32 = jnp.float32


def _departures(dims, consts, st):
    """Seed-style wire placement: one scatter over all ports with a
    dropped write-off slot for idle ports."""
    t = st.now
    m = st.m
    NQ, CAP, L = dims.NQ, dims.CAP, dims.L
    qidx = consts.qidx
    # the fault model moved to compiled schedule tables (netsim/faults);
    # the shared evaluation replaces the seed's static service_period /
    # dead vectors bit-for-bit, and the baseline keeps its seed-style op
    # structure everywhere else
    if dims.FK or dims.flapped:
        per = faults.port_period(dims, consts, t)
        svc = jnp.where(per > 1, (t % jnp.maximum(per, 1)) == 0, True)
    else:
        svc = True
    active = (st.q_size[:NQ] > 0) & svc
    head = st.q_head[:NQ]
    hf = st.q_fields[qidx, head]
    d_flow, d_seq, d_ent, d_ecn, d_ts = (hf[:, i] for i in range(5))
    from repro.netsim import hashing
    qsz = st.q_size[:NQ].astype(F32)
    pmark = jnp.clip((qsz - consts.kmin) / consts.kspan, 0.0, 1.0)
    mark = hashing.uniform01(t * jnp.int32(131071) + qidx,
                             jnp.int32(0xECD) + st.salt) < pmark
    d_ecn = d_ecn | (mark & active).astype(I32)
    if dims.FK or dims.flapped:
        black = (per == 0)[qidx] & active
    else:
        black = jnp.zeros((NQ,), bool)
    emit = active & ~black
    next_q = fabric.route_from_queue(dims, consts, d_flow, d_ent)
    q_head = st.q_head.at[:NQ].set(jnp.where(active, (head + 1) % CAP, head))
    q_size = st.q_size.at[:NQ].add(-active.astype(I32))
    B = dims.QE
    lat = jnp.where(qidx < B, consts.lat_core, consts.lat_edge)
    slot = jnp.where(emit, (t + lat) % L, L)          # L = dropped
    payload = jnp.stack(
        [emit.astype(I32), next_q, d_flow, d_seq, d_ent, d_ecn, d_ts], axis=1)
    infl = st.infl.at[slot, qidx].set(payload, mode="drop")
    m = m._replace(n_black=m.n_black + jnp.sum(black.astype(I32)))
    return st._replace(q_head=q_head, q_size=q_size, infl=infl, m=m)


def _arrivals(dims, consts, st):
    """Seed-style arrivals: full-emitter delivery path, scattered ACK ring
    write, argsort enqueue ranking, three separate trim-ledger scatters."""
    t = st.now
    m = st.m
    NF, NQ, NE, N = dims.NF, dims.NQ, dims.NE, dims.N
    CAP, L, R = dims.CAP, dims.L, dims.R

    arr = st.infl[t % L]
    infl = st.infl.at[t % L].set(0)
    a_valid = arr[:, 0] == 1
    a_dstq, a_flow, a_seq, a_ent, a_ecn, a_ts = (arr[:, i] for i in range(1, 7))
    deliver = a_valid & (a_dstq < 0)
    enq = a_valid & (a_dstq >= 0)

    node = jnp.where(deliver, -a_dstq - 1, 0)
    dflow = jnp.where(deliver, a_flow, NF)
    word, bit = a_seq // 32, a_seq % 32
    old = st.bitmap[dflow, word]
    isnew = deliver & (((old >> bit) & 1) == 0)
    bitmap = st.bitmap.at[dflow, word].add(
        jnp.where(isnew, (1 << bit).astype(I32), 0))
    psz = pkt_size(dims, consts, a_flow, a_seq)
    goodput = st.goodput.at[jnp.where(isnew, a_flow, 0)].add(
        jnp.where(isnew, psz, 0))
    newly_done = (goodput >= consts.size) & ~st.done
    done = st.done | newly_done
    fct = jnp.where(newly_done, t + consts.ret - consts.t_start, st.fct)
    anode = jnp.where(deliver, node, N)               # N = dropped
    aslot = jnp.where(deliver, (t + consts.ret) % R, 0)
    ack_payload = jnp.stack(
        [deliver.astype(I32), a_flow, a_seq, a_ecn, a_ent, a_ts], axis=1)
    ack_ring = st.ack_ring.at[aslot, anode].set(ack_payload, mode="drop")
    m = m._replace(
        delivered_pkts=m.delivered_pkts + jnp.sum(deliver.astype(I32)),
        delivered_bytes=m.delivered_bytes
        + jnp.sum(jnp.where(isnew, psz, 0)).astype(F32),
    )

    # enqueues: stable argsort ranking (the pre-PR scheme)
    q_head, q_size = st.q_head, st.q_size
    edst = jnp.where(enq, a_dstq, NQ)
    order = jnp.argsort(edst)
    ds = edst[order]
    eflow, eseq, eent, eecn, ets = (
        x[order] for x in (a_flow, a_seq, a_ent, a_ecn, a_ts))
    first = jnp.searchsorted(ds, ds, side="left")
    rank = jnp.arange(NE, dtype=first.dtype) - first
    space = CAP - q_size[ds]
    acc = (ds < NQ) & (rank < space)
    pos = (q_head[ds] + q_size[ds] + rank.astype(I32)) % CAP
    row = jnp.where(acc, ds, NQ)
    posw = jnp.where(acc, pos, 0)
    q_fields = st.q_fields.at[row, posw].set(
        jnp.stack([eflow, eseq, eent, eecn, ets], axis=1))
    q_size = q_size + jax.ops.segment_sum(acc.astype(I32), ds,
                                          num_segments=NQ + 1)
    rej = (ds < NQ) & ~acc
    rflow = jnp.where(rej, eflow, NF)
    rbytes = jnp.where(rej, pkt_size(dims, consts, eflow, eseq), 0)
    trim_seen = st.trim_seen.at[rflow].add(rbytes.astype(F32))
    if dims.trimming:
        W, WW = dims.W, dims.WW
        tslot = jnp.where(rej, (t + consts.trim_delay) % R, 0)
        trim_ring = st.trim_ring.at[tslot, rflow, 0].add(rej.astype(I32))
        trim_ring = trim_ring.at[tslot, rflow, 1].add(rbytes)
        wslot = (eseq % W) // 32
        wbit = (eseq % W) % 32
        trim_ring = trim_ring.at[tslot, rflow, 2 + wslot].add(
            jnp.where(rej, (1 << wbit).astype(I32), 0))
        m = m._replace(n_trim=m.n_trim + jnp.sum(rej.astype(I32)))
    else:
        trim_ring = st.trim_ring
        m = m._replace(n_drop=m.n_drop + jnp.sum(rej.astype(I32)))
    return st._replace(
        infl=infl, bitmap=bitmap, goodput=goodput, done=done, fct=fct,
        ack_ring=ack_ring, q_fields=q_fields, q_size=q_size,
        trim_seen=trim_seen, trim_ring=trim_ring, m=m)


def _control(dims, consts, cc_update, st):
    """Seed-style control: five separate ACK-drain scatters, scattered
    sent-slot free, two separate loss slice-writes, histogram scatter."""
    t = st.now
    m = st.m
    NF, N, R, W = dims.NF, dims.N, dims.R, dims.W
    MTU = float(dims.mtu)
    flow_ids = consts.flow_ids

    acks = st.ack_ring[t % R]
    ack_ring = st.ack_ring.at[t % R].set(0)
    v = acks[:, 0] == 1
    idxf = jnp.where(v, acks[:, 1], NF)

    def scat(vals, fill=0):
        return jnp.full((NF + 1,), fill, vals.dtype).at[idxf].set(vals)[:NF]

    has_ack = jnp.zeros((NF + 1,), bool).at[idxf].set(v)[:NF]
    ack_seq = scat(acks[:, 2])
    ack_ecn = jnp.zeros((NF + 1,), bool).at[idxf].set(acks[:, 3] == 1)[:NF]
    ack_ent = scat(acks[:, 4])
    ack_ts = scat(acks[:, 5])
    rtt = jnp.where(has_ack, (t - ack_ts).astype(F32), 0.0)
    ack_bytes = jnp.where(
        has_ack, pkt_size(dims, consts, flow_ids, ack_seq).astype(F32), 0.0)

    tr = st.trim_ring[t % R][:NF]
    trims, tbytes, lbits = tr[:, 0], tr[:, 1].astype(F32), tr[:, 2:]
    cred = st.credit_ring[t % R][:NF]
    trim_ring = st.trim_ring.at[t % R].set(0)
    credit_ring = st.credit_ring.at[t % R].set(0.0)

    aslot2 = ack_seq % W
    cur = st.sent[0, flow_ids, aslot2]
    cur_seq = st.sent[1, flow_ids, aslot2]
    match = has_ack & (cur != 0) & (cur_seq == ack_seq)
    sent = st.sent.at[0, flow_ids, aslot2].set(jnp.where(match, 0, cur))

    wbits = jnp.arange(W, dtype=I32)
    bitsel = (lbits[:, wbits // 32] >> (wbits % 32)) & 1
    lost_mask = (bitsel == 1) & (sent[0, :NF] == 1)
    sent = sent.at[0, :NF].set(jnp.where(lost_mask, 3, sent[0, :NF]))

    started_flows = (t >= consts.t_start) & ~st.done
    to_mask = (sent[0, :NF] == 1) & \
        ((t - sent[2, :NF]).astype(F32) > consts.rto[:, None]) & \
        started_flows[:, None]
    sp_word = sent[1, :NF] // 32
    sp_bit = sent[1, :NF] % 32
    already = ((st.bitmap[:NF][jnp.arange(NF)[:, None], sp_word]
                >> sp_bit) & 1) == 1
    m = m._replace(spurious_retx=m.spurious_retx
                   + jnp.sum((to_mask & already).astype(I32)))
    sent = sent.at[0, :NF].set(jnp.where(to_mask, 3, sent[0, :NF]))
    n_to = jnp.sum(to_mask.astype(I32), axis=1)
    to_bytes = n_to.astype(F32) * MTU
    m = m._replace(n_to=m.n_to + jnp.sum(n_to))
    unacked = jnp.sum((sent[0, :NF] == 1).astype(I32),
                      axis=1).astype(F32) * MTU

    ev = CCEvent(
        has_ack=has_ack, ack_bytes=ack_bytes, ecn=ack_ecn, rtt=rtt,
        ack_entropy=ack_ent, n_trims=trims, trim_bytes=tbytes,
        n_timeouts=n_to, to_bytes=to_bytes, unacked=unacked,
        credit_grant=cred)
    cc = cc_update(consts.cc, st.cc, ev, t)
    lb = reps.on_ack(dims.lb_mode, consts.lb, st.lb, has_ack, ack_ecn,
                     ack_ent, flow_ids, t)
    bins = jnp.clip((rtt * (8.0 / dims.brtt_inter)).astype(I32),
                    0, HIST_BINS - 1)
    m = m._replace(
        rtt_hist=m.rtt_hist.at[jnp.where(has_ack, bins, 0)].add(
            has_ack.astype(I32)),
        n_ack=m.n_ack + jnp.sum(has_ack.astype(I32)))
    return st._replace(
        ack_ring=ack_ring, trim_ring=trim_ring, credit_ring=credit_ring,
        sent=sent, unacked=unacked, cc=cc, lb=lb, m=m)


def _sends(dims, consts, st):
    """Seed-style sends: scatter-built eligibility and emission masks,
    three separate sent-ring component scatters, scattered wire write."""
    t = st.now
    m = st.m
    NF, N, NQ, L, W = dims.NF, dims.N, dims.NQ, dims.L, dims.W
    FMAX, window = dims.FMAX, dims.window
    mtu_i = dims.mtu
    flow_ids = consts.flow_ids
    cc = st.cc

    pace = st.pace_accum
    if dims.paced:
        pace = jnp.minimum(pace + cc.pacing_rate, 4.0 * float(mtu_i))

    done_p = jnp.pad(st.done, (0, 1), constant_values=True)
    unfin = (~done_p[consts.flows_of]) & (consts.flows_of < NF)
    prior_unfin = jnp.cumsum(unfin, axis=1) - unfin.astype(I32)
    win_elig = jnp.full((NF + 1,), False).at[consts.flows_of.reshape(-1)].set(
        (prior_unfin < window).reshape(-1))[:NF]

    started = (t >= consts.t_start) & ~st.done & win_elig
    is_retx = st.sent[0, :NF] == 3
    has_retx = jnp.any(is_retx, axis=1)
    retx_slot = jnp.argmax(is_retx, axis=1)
    retx_seq = st.sent[1, flow_ids, retx_slot]
    new_seq = st.next_seq
    new_slot = new_seq % W
    new_ok = (new_seq * mtu_i < consts.size) & \
        (st.sent[0, flow_ids, new_slot] == 0)
    seq_emit = jnp.where(has_retx, retx_seq, new_seq)
    nsize = pkt_size(dims, consts, flow_ids, seq_emit).astype(F32)
    win_ok = st.unacked + nsize <= cc.cwnd
    credit_ok = True
    if dims.credit_based:
        credit_ok = (cc.credits >= nsize) | (cc.spec_budget >= nsize)
    pace_ok = (pace >= nsize) if dims.paced else True
    elig = started & (has_retx | new_ok) & win_ok & credit_ok & pace_ok & \
        (nsize > 0)

    E = jnp.pad(elig, (0, 1))[consts.flows_of]
    keys = (jnp.arange(FMAX, dtype=I32)[None, :] - st.rr_send[:, None]) % FMAX
    keys = jnp.where(E, keys, FMAX + 1)
    sel = jnp.argmin(keys, axis=1)
    has_s = jnp.any(E, axis=1)
    sflow = jnp.where(has_s, consts.flows_of[consts.node_ids, sel], NF)
    rr_send = jnp.where(has_s, (sel.astype(I32) + 1) % FMAX, st.rr_send)

    emit_mask = jnp.zeros((NF + 1,), bool).at[sflow].set(has_s)[:NF]
    lb, entropy = reps.on_send(dims.lb_mode, consts.lb, st.lb, emit_mask,
                               seq_emit, flow_ids, t)
    first_q = fabric.route_from_sender(dims, consts, flow_ids, entropy)

    send_slot = jnp.where(has_s, (t + consts.lat_send) % L, L)
    sf = jnp.clip(sflow, 0, NF - 1)
    spay = jnp.stack([
        has_s.astype(I32), first_q[sf], sflow, seq_emit[sf], entropy[sf],
        jnp.zeros((N,), I32), jnp.full((N,), 1, I32) * t], axis=1)
    infl = st.infl.at[send_slot, NQ + consts.node_ids].set(spay, mode="drop")

    eslot = seq_emit % W
    eflow2 = jnp.where(emit_mask, flow_ids, NF)
    sent = st.sent.at[0, eflow2, eslot].set(
        jnp.where(emit_mask, 1, st.sent[0, eflow2, eslot]))
    sent = sent.at[1, eflow2, eslot].set(
        jnp.where(emit_mask, seq_emit, sent[1, eflow2, eslot]))
    sent = sent.at[2, eflow2, eslot].set(
        jnp.where(emit_mask, t, sent[2, eflow2, eslot]))
    is_new_send = emit_mask & ~has_retx
    next_seq = st.next_seq + is_new_send.astype(I32)
    m = m._replace(n_retx=m.n_retx
                   + jnp.sum((emit_mask & has_retx).astype(I32)))

    spend = jnp.where(emit_mask, nsize, 0.0)
    if dims.credit_based:
        use_credit = cc.credits >= nsize
        cc = cc._replace(
            credits=cc.credits - spend * use_credit,
            spec_budget=cc.spec_budget - spend * (~use_credit))
    if dims.paced:
        pace = pace - spend
    return st._replace(
        infl=infl, sent=sent, next_seq=next_seq, rr_send=rr_send,
        pace_accum=pace, cc=cc, lb=lb, m=m)


def build_legacy(cfg, wl):
    """An engine.Sim whose step uses the pre-PR op structure (run it with
    perf._run_k1_ungated for the full legacy baseline)."""
    import dataclasses
    sim = engine.build(cfg, wl)
    cc_update = registry.get(cfg.algo, cfg.cc_backend)
    _, arb = enqueue_arb_ops.get(cfg.fabric_backend)
    dims, consts = sim.dims, sim.consts

    def step(st):
        st = _departures(dims, consts, st)
        st = _arrivals(dims, consts, st)
        st = _control(dims, consts, cc_update, st)
        st = sender.grants(dims, consts, st, arb=arb)
        st = _sends(dims, consts, st)
        st = metrics.account(dims, consts, st)
        return st._replace(now=st.now + 1)

    return dataclasses.replace(sim, step=step)
