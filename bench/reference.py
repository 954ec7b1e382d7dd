"""Plain reference of the slotted fat-tree transport simulator.

The benchmark's yardstick for ``correct``: the same model of the fabric,
SMaRTT and REPS as the program under test, written from the model's
description and nothing of the program.  It imports no code of the
program and takes nothing the program has built.  It steps every tick one
by one (no supersteps, no time leaps, no lane batching), keeps the state
in one flat dict, and writes the queues, rings and per-flow tables with
plain scatters and sorts.  Its final state has the program's layout,
field for field, so that the two can be compared whole.

The model, one tick = one MTU time, all links one rate:

1. departures: every non-empty output port sends its head packet; RED
   marks it with probability ``clip((q - kmin) / kspan, 0, 1)`` drawn from
   a counter hash of (tick, port, salt); the packet is routed at the
   switch the port's wire feeds (down inside the subtree, else an ECMP
   hash of its entropy with the switch's salt) and put on that wire.
2. arrivals: packets landing this tick at a host are delivered (receiver
   bitmap dedupe, goodput, completion, an ACK on the return ring);
   packets landing at a switch join their next queue in emitter order,
   and those that find it full are trimmed: a header reaches the sender
   ``trim_delay`` ticks later.
3. control: ACKs and trim notices free or lose sent-ring slots, the RTO
   fires on old outstanding slots, SMaRTT (paper Alg. 1-3) updates each
   window and REPS (Alg. 4) its cached entropy.
4. sends: each NIC sends one packet of one admitted flow (round robin
   over its flows), a retransmission before new data, REPS picks the
   entropy, and the sent ring records it.
5. metrics: queue occupancy sum and maximum.

``F`` is the float type of every float leaf.  ``float32`` is what the
configuration states; the control of the benchmark's check runs the same
reference at ``bfloat16``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

I32 = jnp.int32
U32 = jnp.uint32
HIST_BINS = 64
GOODPUT_BINS = 64
HDR_BYTES = 64.0
REF_BDP_BYTES = 100e9 / 8.0 * 12e-6      # paper Sec. 3.5: 100 Gb/s, 12 us


# --------------------------------------------------------------------------
# counter hash (murmur3 finalizer), the model's source of randomness
# --------------------------------------------------------------------------


def _mix(x):
    x = x.astype(U32)
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _hash2(a, b):
    return _mix(jnp.asarray(a).astype(U32) * np.uint32(0x9E3779B9)
                + _mix(jnp.asarray(b)))


def _uniform01(a, b):
    return _mix(_hash2(a, b)).astype(jnp.float32) * \
        jnp.float32(1.0 / 4294967296.0)


def _word_of(words, idx):
    """``words[f, idx[f, j]]`` for a [F, K] table of a few words: a select
    per word, which the TPU runs densely (a [F, W] gather it does not)."""
    out = jnp.zeros(idx.shape, words.dtype)
    for k in range(words.shape[1]):
        out = jnp.where(idx == k, words[:, k:k + 1], out)
    return out


# --------------------------------------------------------------------------
# fabric and timing, from the configuration's numbers
# --------------------------------------------------------------------------


class Fabric:
    """A three-tier fat tree: ``pods`` pods of ``racks / pods`` racks of
    ``nodes_per_rack`` hosts; every rack has one uplink to each of its
    pod's ``uplinks`` aggregation (T1) switches; T1 switch ``a`` of every
    pod has ``core_uplinks`` uplinks, to cores ``a * core_uplinks + j``.

    Output ports are numbered block by block: rack uplinks, T1 uplinks,
    core downlinks, T1 downlinks, then one rack downlink per host (the
    last ``N``).  Emitters are the ports, then the ``N`` host NICs.
    Switch ids: racks, then T1 switches (pod-major), then cores."""

    def __init__(self, tree: dict):
        self.P = int(tree["racks"])
        self.M = int(tree["nodes_per_rack"])
        self.U1 = int(tree["uplinks"])
        self.G = int(tree["pods"])
        self.U2 = int(tree["core_uplinks"])
        if self.G < 1 or self.U2 < 1 or self.P % self.G:
            raise ValueError(f"the reference models three-tier trees: {tree}")
        self.N = self.P * self.M
        self.Pg = self.P // self.G
        self.NA = self.G * self.U1
        self.C = self.U1 * self.U2
        self.b_t1up = self.P * self.U1
        self.b_t2dn = self.b_t1up + self.NA * self.U2
        self.b_t1dn = self.b_t2dn + self.C * self.G
        self.b_t0dn = self.b_t1dn + self.NA * self.Pg
        self.NQ = self.b_t0dn + self.N
        self.NE = self.NQ + self.N
        self.QE = self.NQ - self.N
        self.NSW = self.P + self.NA + self.C
        # the switch each port's wire feeds (ports facing hosts: 0, unused)
        feeds = np.zeros(self.NQ, np.int64)
        for r in range(self.P):
            for a in range(self.U1):
                feeds[r * self.U1 + a] = self.P + (r // self.Pg) * self.U1 + a
        for s1 in range(self.NA):
            for j in range(self.U2):
                feeds[self.b_t1up + s1 * self.U2 + j] = \
                    self.P + self.NA + (s1 % self.U1) * self.U2 + j
        for c in range(self.C):
            for g in range(self.G):
                feeds[self.b_t2dn + c * self.G + g] = \
                    self.P + g * self.U1 + c // self.U2
        for s1 in range(self.NA):
            for i in range(self.Pg):
                feeds[self.b_t1dn + s1 * self.Pg + i] = \
                    (s1 // self.U1) * self.Pg + i
        self.feeds = feeds
        self.salt = (np.arange(self.NSW, dtype=np.uint32) * np.uint32(0x9E37)
                     + np.uint32(0x1234))

    def route(self, sw, d, ent):
        """Next output port at switch ``sw`` for a packet to host ``d``
        with entropy ``ent`` (jnp arrays of one shape)."""
        h = _hash2(ent, jnp.asarray(self.salt)[sw])
        rack_of_d = d // self.M
        pod_of_d = d // (self.M * self.Pg)
        # rack switch
        at_rack = jnp.where(rack_of_d == sw, self.b_t0dn + d,
                            sw * self.U1 + (h % np.uint32(self.U1)).astype(I32))
        # aggregation switch
        s1 = sw - self.P
        g = s1 // self.U1
        at_t1 = jnp.where(
            pod_of_d == g, self.b_t1dn + s1 * self.Pg + rack_of_d - g * self.Pg,
            self.b_t1up + s1 * self.U2 + (h % np.uint32(self.U2)).astype(I32))
        # core switch
        c = sw - self.P - self.NA
        at_core = self.b_t2dn + c * self.G + pod_of_d
        return jnp.where(sw < self.P, at_rack,
                         jnp.where(sw < self.P + self.NA, at_t1, at_core))


def timing(link: dict) -> dict:
    """Tick-domain latencies.  A data path through ``h`` queues costs
    ``(1 + l + s) h + (1 + l)`` ticks; a control return ``(l + s) h + l``."""
    tick_ns = link["mtu_bytes"] * 8.0 / link["rate_gbps"]
    lt = max(1, round(link["link_latency_ns"] / tick_ns))
    sl = max(1, round(link["switch_latency_ns"] / tick_ns))

    def fwd(h):
        return (1 + lt + sl) * h + (1 + lt)

    def ret(h):
        return (lt + sl) * h + lt

    hop = 1 + lt + sl
    return dict(l=lt, s=sl, hop=hop, fwd=(fwd(1), fwd(3), fwd(5)),
                ret=ret(5), brtt=fwd(5) + ret(5), trim_delay=ret(5) + hop)


# --------------------------------------------------------------------------
# the reference simulator
# --------------------------------------------------------------------------


class Reference:
    """One deployment: fabric, link, flow table and SMaRTT/REPS constants.

    ``flows`` holds numpy columns ``src, dst, size, t_start, order`` and
    optionally ``dep_par, dep_thr`` ([F, D], -1 = free slot).  ``algo``
    holds the SMaRTT constants (paper Sec. 3, as the configuration file
    states them), ``params`` the run's tunables (``start_cwnd_mult``, ``kmin_frac``,
    ``kmax_frac``, ``num_entropies``, ``rto_mult``)."""

    def __init__(self, tree: dict, link: dict, flows: dict, algo: dict,
                 params: dict, max_ticks: int, F=jnp.float32):
        self.fab = fab = Fabric(tree)
        self.tm = tm = timing(link)
        self.F = F
        self.max_ticks = int(max_ticks)
        self.mtu = mtu = int(link["mtu_bytes"])
        src = np.asarray(flows["src"], np.int64)
        dst = np.asarray(flows["dst"], np.int64)
        size = np.asarray(flows["size"], np.int64)
        self.NF = NF = len(src)
        N = fab.N
        # sizes the model states: a port buffers one BDP of packets; the
        # sent ring (a bounded retransmit buffer: a new send waits for its
        # slot) has a power of two of slots, 1.5 x the largest window or
        # the flow's packets, whichever is less, and at least 32; the wire
        # and control rings are long enough for the longest delay
        brtt = tm["brtt"]
        self.CAP = brtt
        max_pkts = int(math.ceil(size.max() / mtu))
        W = int(2 ** math.ceil(math.log2(max(1.5 * 1.25 * brtt, 32))))
        self.W = min(W, int(2 ** math.ceil(math.log2(max(max_pkts, 32)))))
        self.WW = self.W // 32
        self.L = tm["hop"] + 2
        self.R = max(tm["ret"], tm["trim_delay"]) + tm["hop"] + 4
        self.MAXW = (max_pkts + 31) // 32
        # per-sender flow lists, in (order, flow id) order
        order = np.asarray(flows["order"], np.int64)
        per = [[] for _ in range(N)]
        for f in sorted(range(NF), key=lambda f: (order[f], f)):
            per[src[f]].append(f)
        self.FMAX = max(1, max(len(p) for p in per))
        flows_of = np.full((N, self.FMAX), NF, np.int64)
        for n, fl in enumerate(per):
            flows_of[n, :len(fl)] = fl
        dep_par = flows.get("dep_par")
        self.D = 0 if dep_par is None else int(np.asarray(dep_par).shape[1])
        if self.D:
            dep_par = np.asarray(dep_par, np.int64).copy()
            dep_thr = np.asarray(flows["dep_thr"], np.int64).copy()
            dep_thr[dep_par < 0] = 0
            dep_par[dep_par < 0] = NF
        # hop class of each flow: same rack, same pod, across the core
        sr, dr = src // fab.M, dst // fab.M
        fwd = np.where(sr == dr, tm["fwd"][0],
                       np.where(sr // fab.Pg == dr // fab.Pg, tm["fwd"][1],
                                tm["fwd"][2]))
        bdp = float(brtt * mtu)
        gam = bdp / REF_BDP_BYTES
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        flow_brtt = f32((fwd + tm["ret"]).astype(np.float32))
        trtt = flow_brtt * f32(algo["target_mult"])
        cc = dict(
            mtu=f32(float(mtu)), bdp=f32(bdp),
            maxcwnd=f32(algo["maxcwnd_mult"] * bdp), mincwnd=f32(float(mtu)),
            brtt=flow_brtt, trtt=trtt, fd=f32(algo["fd"]), md=f32(algo["md"]),
            fi=f32(algo["fi"] * gam),
            mi=flow_brtt / jnp.maximum(trtt - flow_brtt, 1e-6) * f32(gam),
            k_fast=f32(algo["k_fast"]), qa_scaling=f32(algo["qa_scaling"]),
            wtd_alpha=f32(algo["wtd_alpha"]),
            wtd_thresh=f32(algo["wtd_thresh"]),
            fi_rtt_tol=f32(algo["fi_rtt_tol"]))
        self._base = dict(
            src=jnp.asarray(src, I32), dst=jnp.asarray(dst, I32),
            size=jnp.asarray(size, I32),
            t_start=jnp.asarray(np.asarray(flows["t_start"]), I32),
            flows_of=jnp.asarray(flows_of, I32),
            dep_par=jnp.asarray(dep_par if self.D else np.zeros((NF, 0)), I32),
            dep_thr=jnp.asarray(dep_thr if self.D else np.zeros((NF, 0)), I32),
            cc={k: v.astype(F) for k, v in cc.items()},
        )
        self._trtt, self._bdp = trtt, bdp
        self.react_every = algo["react_every"]
        self.c = self.consts(params)

    def consts(self, params: dict) -> dict:
        """The run's constants for one set of tunables (one point of a
        sweep): RED thresholds, RTO, initial window, entropies."""
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        kmin = params["kmin_frac"] * self.CAP
        kmax = params["kmax_frac"] * self.CAP
        return dict(
            self._base, kmin=f32(kmin), kspan=f32(kmax - kmin),
            rto=f32(params["rto_mult"]) * self._trtt,
            start_cwnd=f32(params["start_cwnd_mult"] * self._bdp),
            n_ent=jnp.asarray(int(params["num_entropies"]), I32))

    # ---- state ----------------------------------------------------------

    def init(self, c: dict, salt) -> dict:
        fab, NF, N, F = self.fab, self.NF, self.fab.N, self.F
        z = lambda shape, dt=I32: jnp.zeros(shape, dt)
        rand = (_hash2(jnp.arange(NF, dtype=I32), jnp.int32(0))
                % c["n_ent"].astype(U32)).astype(I32)
        st = {
            "now": z(()), "salt": jnp.asarray(salt, I32),
            "q_fields": z((fab.NQ + 1, self.CAP, 5)),
            "q_head": z((fab.NQ + 1,)), "q_size": z((fab.NQ + 1,)),
            "infl": z((self.L, fab.NE, 7)), "ack_ring": z((self.R, N, 6)),
            "trim_ring": z((self.R, NF + 1, 2 + self.WW)),
            "credit_ring": z((self.R, NF + 1), F),
            "sent": z((3, NF + 1, self.W)),
            "next_seq": z((NF,)), "unacked": z((NF,), F),
            "done": z((NF,), bool), "fct": jnp.full((NF,), -1, I32),
            "goodput": z((NF,)), "bitmap": z((NF + 1, self.MAXW)),
            "granted": z((NF,), F), "trim_seen": z((NF + 1,), F),
            "rr_recv": z((N,)), "rr_send": z((N,)),
            "pace_accum": z((NF,), F), "rto_backoff": z((NF,)),
            # SMaRTT window state, and the unused fields of the other
            # algorithms at their initial values
            "cc.cwnd": jnp.broadcast_to(c["start_cwnd"].astype(F), (NF,)),
            "cc.acked": z((NF,), F), "cc.qa_end": z((NF,), F),
            "cc.trigger_qa": z((NF,), bool),
            "cc.bytes_to_ignore": z((NF,), F), "cc.bytes_ignored": z((NF,), F),
            "cc.fi_count": z((NF,), F), "cc.fi_active": z((NF,), bool),
            "cc.avg_wtd": z((NF,), F), "cc.ack_count": z((NF,)),
            "cc.last_dec": jnp.full((NF,), -1e9, F),
            "cc.bw_est": jnp.full((NF,), float(self.mtu), F),
            "cc.rtprop": c["cc"]["brtt"].astype(F),
            "cc.win_delivered": z((NF,), F), "cc.win_end": z((NF,), F),
            "cc.pacing_rate": z((NF,), F), "cc.credits": z((NF,), F),
            "cc.spec_budget": jnp.full((NF,), float(self.tm["brtt"] * self.mtu),
                                       F),
            "lb.next_entropy": rand, "lb.cached_entropy": rand,
            "lb.explore_sent": z((NF,)), "lb.spray_ctr": z((NF,)),
            "lb.plb_entropy": rand, "lb.plb_marked": z((NF,), F),
            "lb.plb_total": z((NF,), F), "lb.plb_congested": z((NF,)),
            "lb.plb_round_end": z((NF,), F),
            "m.n_trim": z(()), "m.n_drop": z(()), "m.n_black": z(()),
            "m.n_to": z(()), "m.n_retx": z(()), "m.n_ack": z(()),
            "m.delivered_pkts": z(()), "m.delivered_bytes": z((), F),
            "m.rtt_hist": z((HIST_BINS,)), "m.q_sum": z((), F),
            "m.q_max": z(()), "m.spurious_retx": z(()),
            "m.delivered_bytes_fault": z((), F),
            "m.goodput_hist": z((GOODPUT_BINS,), F),
        }
        return st

    # ---- one tick -------------------------------------------------------

    def tick(self, c: dict, st: dict) -> dict:
        st = dict(st)
        self._departures(c, st)
        self._arrivals(c, st)
        self._control(c, st)
        self._sends(c, st)
        q = st["q_size"][:self.fab.NQ]
        st["m.q_sum"] = st["m.q_sum"] + jnp.sum(q).astype(self.F)
        st["m.q_max"] = jnp.maximum(st["m.q_max"], jnp.max(q))
        st["now"] = st["now"] + 1
        return st

    def _departures(self, c, st):
        fab, F = self.fab, self.F
        NQ, QE, CAP, L = fab.NQ, fab.QE, self.CAP, self.L
        t = st["now"]
        ports = jnp.arange(NQ, dtype=I32)
        qs = st["q_size"][:NQ]
        active = qs > 0
        head = st["q_head"][:NQ]
        pkt = st["q_fields"][ports, head]
        flow, seq, ent, ecn, ts = (pkt[:, i] for i in range(5))
        pmark = jnp.clip((qs.astype(F) - c["kmin"].astype(F))
                         / c["kspan"].astype(F), 0.0, 1.0)
        u = _uniform01(t * jnp.int32(131071) + ports,
                       jnp.int32(0xECD) + st["salt"])
        ecn = ecn | (active & (u.astype(F) < pmark)).astype(I32)
        d = c["dst"][jnp.clip(flow, 0, self.NF - 1)]
        nxt = fab.route(jnp.asarray(fab.feeds, I32), d, ent)
        nxt = jnp.where(ports >= QE, -(d + 1), nxt)
        pay = jnp.stack([jnp.ones_like(flow), nxt, flow, seq, ent, ecn, ts], 1)
        pay = jnp.where(active[:, None], pay, 0)
        lt, sl = self.tm["l"], self.tm["s"]
        infl = st["infl"].at[(t + lt + sl) % L, :QE].set(pay[:QE])
        st["infl"] = infl.at[(t + lt) % L, QE:NQ].set(pay[QE:])
        st["q_head"] = st["q_head"].at[:NQ].set(
            jnp.where(active, (head + 1) % CAP, head))
        st["q_size"] = st["q_size"].at[:NQ].add(-active.astype(I32))

    def _arrivals(self, c, st):
        fab, F = self.fab, self.F
        NF, NQ, QE, CAP, mtu = self.NF, fab.NQ, fab.QE, self.CAP, self.mtu
        t = st["now"]
        slot = t % self.L
        arr = st["infl"][slot]
        st["infl"] = st["infl"].at[slot].set(0)

        # deliveries: the last N ports face the hosts, one each
        dl = arr[QE:NQ]
        dv = (dl[:, 0] == 1) & (dl[:, 1] < 0)
        flow, seq, ent, ecn, ts = (dl[:, i] for i in range(2, 7))
        fi = jnp.where(dv, flow, NF + 1)                 # NF + 1: dropped
        word, bit = seq // 32, seq % 32
        seen = (st["bitmap"][jnp.clip(fi, 0, NF), word] >> bit) & 1
        new = dv & (seen == 0)
        fnew = jnp.where(new, flow, NF + 1)
        st["bitmap"] = st["bitmap"].at[fnew, word].add(
            jnp.left_shift(1, bit), mode="drop")
        fsize = c["size"][jnp.clip(flow, 0, NF - 1)]
        psz = jnp.where(new, jnp.clip(fsize - seq * mtu, 0, mtu), 0)
        goodput = st["goodput"].at[fnew].add(psz, mode="drop")
        newly = (goodput >= c["size"]) & ~st["done"]
        st["goodput"] = goodput
        st["done"] = st["done"] | newly
        st["fct"] = jnp.where(newly, t + self.tm["ret"] - c["t_start"],
                              st["fct"])
        ack = jnp.stack([jnp.ones_like(flow), flow, seq, ecn, ent, ts], 1)
        st["ack_ring"] = st["ack_ring"].at[(t + self.tm["ret"]) % self.R].set(
            jnp.where(dv[:, None], ack, 0))
        st["m.delivered_pkts"] = st["m.delivered_pkts"] + jnp.sum(
            dv.astype(I32))
        st["m.delivered_bytes"] = st["m.delivered_bytes"] + \
            jnp.sum(psz).astype(F)

        # arrivals at switches join their next queue, in emitter order
        eids = np.concatenate([np.arange(QE), np.arange(NQ, fab.NE)])
        ea = arr[eids]
        want = (ea[:, 0] == 1) & (ea[:, 1] >= 0)
        qd = jnp.where(want, ea[:, 1], NQ)
        order = jnp.argsort(qd, stable=True)
        sq = qd[order]
        rank_sorted = jnp.arange(len(eids), dtype=I32) - jnp.searchsorted(
            sq, sq, side="left").astype(I32)
        rank = jnp.zeros(len(eids), I32).at[order].set(rank_sorted)
        qsz = st["q_size"][qd]
        acc = want & (rank < CAP - qsz)
        pos = (st["q_head"][qd] + qsz + rank) % CAP
        qa = jnp.where(acc, qd, NQ + 1)
        eflow, eseq = ea[:, 2], ea[:, 3]
        st["q_fields"] = st["q_fields"].at[qa, pos].set(ea[:, 2:7], mode="drop")
        st["q_size"] = st["q_size"].at[qa].add(1, mode="drop")
        # a full queue trims the packet; its header reaches the sender
        rej = want & ~acc
        rf = jnp.where(rej, eflow, NF + 1)
        rbytes = jnp.clip(c["size"][jnp.clip(eflow, 0, NF - 1)] - eseq * mtu,
                          0, mtu)
        ws = eseq % self.W
        ts_ = (t + self.tm["trim_delay"]) % self.R
        tr = st["trim_ring"]
        tr = tr.at[ts_, rf, 0].add(1, mode="drop")
        tr = tr.at[ts_, rf, 1].add(rbytes, mode="drop")
        st["trim_ring"] = tr.at[ts_, rf, 2 + ws // 32].add(
            jnp.left_shift(1, ws % 32), mode="drop")
        st["m.n_trim"] = st["m.n_trim"] + jnp.sum(rej.astype(I32))

    def _control(self, c, st):
        F = self.F
        NF, W, mtu = self.NF, self.W, self.mtu
        t = st["now"]
        rs = t % self.R
        acks = st["ack_ring"][rs]
        st["ack_ring"] = st["ack_ring"].at[rs].set(0)
        af = jnp.where(acks[:, 0] == 1, acks[:, 1], NF + 1)
        per_flow = lambda col: jnp.zeros(NF, I32).at[af].set(col, mode="drop")
        has = jnp.zeros(NF, bool).at[af].set(True, mode="drop")
        aseq = per_flow(acks[:, 2])
        aecn = per_flow(acks[:, 3]) == 1
        aent = per_flow(acks[:, 4])
        ats = per_flow(acks[:, 5])
        rtt = jnp.where(has, (t - ats).astype(F), 0.0)
        abytes = jnp.where(has, jnp.clip(c["size"] - aseq * mtu, 0, mtu)
                           .astype(F), 0.0)
        tr = st["trim_ring"][rs, :NF]
        st["trim_ring"] = st["trim_ring"].at[rs].set(0)
        st["credit_ring"] = st["credit_ring"].at[rs].set(0.0)
        ntrim = tr[:, 0]
        tbytes = tr[:, 1].astype(F)
        started = (t >= c["t_start"]) & ~st["done"]

        # sent ring: ACK frees its slot, a trim notice marks it lost, the
        # RTO marks old outstanding slots lost
        s0, s1, s2 = (st["sent"][i, :NF] for i in range(3))
        lane = jnp.arange(W, dtype=I32)[None, :]
        hit = lane == (aseq % W)[:, None]
        match = has & jnp.any(hit & (s0 != 0) & (s1 == aseq[:, None]), axis=1)
        state = jnp.where(match[:, None] & hit, 0, s0)
        lbit = (_word_of(tr[:, 2:], lane // 32) >> (lane % 32)) & 1
        state = jnp.where((lbit == 1) & (state == 1), 3, state)
        rto = c["rto"].astype(F)
        fire = (state == 1) & ((t - s2).astype(F) > rto[:, None]) & \
            started[:, None]
        held = (_word_of(st["bitmap"][:NF], s1 // 32) >> (s1 % 32)) & 1
        state = jnp.where(fire, 3, state)
        n_to = jnp.sum(fire.astype(I32), axis=1)
        st["sent"] = st["sent"].at[0, :NF].set(state)
        st["m.spurious_retx"] = st["m.spurious_retx"] + jnp.sum(
            (fire & (held == 1)).astype(I32))
        st["m.n_to"] = st["m.n_to"] + jnp.sum(n_to)
        unacked = jnp.sum((state == 1).astype(I32), axis=1).astype(F) * \
            float(mtu)
        st["unacked"] = unacked
        self._smartt(c["cc"], st, has, abytes, aecn, rtt, ntrim, tbytes, n_to,
                     n_to.astype(F) * float(mtu), unacked, t)
        # REPS (Alg. 4 l. 12-17): a marked ACK takes a fresh entropy, a
        # clean one recycles its own
        n = c["n_ent"]
        marked = has & aecn
        clean = has & ~aecn
        nxt = st["lb.next_entropy"]
        st["lb.cached_entropy"] = jnp.where(
            marked, nxt % n, jnp.where(clean, aent, st["lb.cached_entropy"]))
        st["lb.next_entropy"] = nxt + marked.astype(I32)
        bins = jnp.clip((rtt * (8.0 / self.tm["brtt"])).astype(I32), 0,
                        HIST_BINS - 1)
        st["m.rtt_hist"] = st["m.rtt_hist"] + jnp.sum(
            (has[:, None] & (bins[:, None] == jnp.arange(HIST_BINS)))
            .astype(I32), axis=0)
        st["m.n_ack"] = st["m.n_ack"] + jnp.sum(has.astype(I32))

    def _smartt(self, p, st, has, abytes, ecn, ev_rtt, ntrim, tbytes, n_to,
                to_bytes, unacked, t):
        """SMaRTT, paper Alg. 1 (with QuickAdapt, Alg. 2, and
        FastIncrease, Alg. 3): the ACK first, then trims and timeouts."""
        F = self.F
        now = jnp.asarray(t, jnp.float32).astype(F)
        g = lambda k: st["cc." + k]
        cw, acked, qa_end, trig = g("cwnd"), g("acked"), g("qa_end"), \
            g("trigger_qa")
        bti, bi = g("bytes_to_ignore"), g("bytes_ignored")
        fic, fia, wtd = g("fi_count"), g("fi_active"), g("avg_wtd")

        def quick_adapt(cw, acked, qa_end, trig, bti, bi, gate):
            edge = gate & (now >= qa_end)
            fire = edge & trig & (qa_end != 0.0)
            cw = jnp.where(fire, jnp.maximum(acked, p["mtu"]) * p["qa_scaling"],
                           cw)
            bti = jnp.where(fire, unacked, bti)
            bi = jnp.where(fire, 0.0, bi).astype(F)
            trig = trig & ~fire
            qa_end = jnp.where(edge, now + p["trtt"], qa_end)
            acked = jnp.where(edge, 0.0, acked).astype(F)
            return cw, acked, qa_end, trig, bti, bi, fire

        size = jnp.where(has, abytes, 0.0).astype(F)
        acked = acked + size
        bi = bi + size
        act = has & ~(bi < bti)
        ack_count = g("ack_count") + act.astype(I32)
        react = act & (ack_count % max(int(self.react_every), 1) == 0)
        wtd = jnp.where(act, p["wtd_alpha"] * ecn.astype(F)
                        + (1.0 - p["wtd_alpha"]) * wtd, wtd)
        can_dec = wtd >= p["wtd_thresh"]
        cw, acked, qa_end, trig, bti, bi, adapted = quick_adapt(
            cw, acked, qa_end, trig, bti, bi, act)
        # FastIncrease
        near = act & ~ecn & (ev_rtt <= p["brtt"] * p["fi_rtt_tol"] + 1.0)
        count = jnp.where(near, fic + size, 0.0).astype(F)
        finc = near & ((count > cw) | fia)
        cw = jnp.where(finc, cw + p["k_fast"] * p["mtu"], cw)
        fia = (act & finc) | (~act & fia)
        fic = jnp.where(act, count, fic)
        # the four window rules, Eq. 1-4
        go = react & ~(adapted | finc)
        rtt = jnp.maximum(ev_rtt, 1e-6).astype(F)
        cwm = jnp.maximum(cw, 1.0).astype(F)
        fd = cwm / p["bdp"] * p["fd"] * size
        md = jnp.minimum(size, (rtt - p["trtt"]) / rtt * p["md"] * size)
        fi = size / cwm * p["mtu"] * p["fi"]
        mi = jnp.minimum(size, (p["trtt"] - rtt) / rtt * size / cwm
                         * p["mtu"] * p["mi"])
        low = rtt <= p["trtt"]
        is_fd = go & ecn & low & can_dec
        is_md = go & ecn & ~low & can_dec
        is_fi = go & ~ecn & ~low
        is_mi = go & ~ecn & low
        cw = cw + (-fd * is_fd - (md + fd) * is_md + fi * is_fi
                   + (mi + fi) * is_mi)
        # trims and timeouts (l. 28-35); a trimmed header is a received
        # control packet too
        lost = (ntrim + n_to) > 0
        hdr = HDR_BYTES * ntrim.astype(F)
        acked = acked + hdr
        bi = bi + hdr
        cw = cw - jnp.where(lost, tbytes + to_bytes, 0.0)
        trig = trig | lost
        cw, acked, qa_end, trig, bti, bi, _ = quick_adapt(
            cw, acked, qa_end, trig, bti, bi, lost & (bi >= bti))
        cw = jnp.clip(cw, p["mincwnd"], p["maxcwnd"])
        st.update({"cc.cwnd": cw, "cc.acked": acked, "cc.qa_end": qa_end,
                   "cc.trigger_qa": trig, "cc.bytes_to_ignore": bti,
                   "cc.bytes_ignored": bi, "cc.fi_count": fic,
                   "cc.fi_active": fia, "cc.avg_wtd": wtd,
                   "cc.ack_count": ack_count})

    def _sends(self, c, st):
        fab, F = self.fab, self.F
        NF, N, W, mtu, L = self.NF, fab.N, self.W, self.mtu, self.L
        t = st["now"]
        fid = jnp.arange(NF, dtype=I32)
        live = (t >= c["t_start"]) & ~st["done"]
        if self.D:
            gp = jnp.concatenate([st["goodput"], jnp.zeros(1, I32)])
            ok = (c["dep_par"] == NF) | (gp[c["dep_par"]] >= c["dep_thr"])
            live = live & jnp.all(ok, axis=1)
        s0, s1 = st["sent"][0, :NF], st["sent"][1, :NF]
        lost = s0 == 3
        has_retx = jnp.any(lost, axis=1)
        lane = jnp.arange(W, dtype=I32)[None, :]
        first = lane == jnp.argmax(lost, axis=1)[:, None]
        rseq = jnp.sum(jnp.where(first, s1, 0), axis=1)
        nseq = st["next_seq"]
        free = jnp.any((lane == (nseq % W)[:, None]) & (s0 == 0), axis=1)
        new_ok = (nseq * mtu < c["size"]) & free
        seq = jnp.where(has_retx, rseq, nseq)
        nsize = jnp.clip(c["size"] - seq * mtu, 0, mtu).astype(F)
        elig = live & (has_retx | new_ok) & \
            (st["unacked"] + nsize <= st["cc.cwnd"]) & (nsize > 0)
        # each NIC: round robin over its flows, from its cursor
        K = self.FMAX
        E = jnp.concatenate([elig, jnp.zeros(1, bool)])[c["flows_of"]]
        keys = jnp.where(E, (jnp.arange(K, dtype=I32) - st["rr_send"][:, None])
                         % K, K + 1)
        sel = jnp.argmin(keys, axis=1).astype(I32)
        anyf = jnp.any(E, axis=1)
        sflow = jnp.where(anyf, c["flows_of"][jnp.arange(N), sel], NF)
        st["rr_send"] = jnp.where(anyf, (sel + 1) % K, st["rr_send"])
        emit = jnp.zeros(NF + 1, bool).at[sflow].set(True)[:NF]
        # REPS (Alg. 4 l. 5-9): explore the first BDP of packets, then
        # recycle the cached entropy
        n = c["n_ent"]
        explore = emit & (seq < self.tm["brtt"]) & (st["lb.explore_sent"] < n)
        ent = jnp.where(explore, st["lb.next_entropy"] % n,
                        st["lb.cached_entropy"] % n)
        st["lb.next_entropy"] = st["lb.next_entropy"] + explore.astype(I32)
        st["lb.explore_sent"] = st["lb.explore_sent"] + explore.astype(I32)
        sf = jnp.clip(sflow, 0, NF - 1)
        first = fab.route(c["src"][sf] // fab.M, c["dst"][sf], ent[sf])
        pay = jnp.stack([jnp.ones(N, I32), first, sflow, seq[sf], ent[sf],
                         jnp.zeros(N, I32), jnp.broadcast_to(t, (N,))], 1)
        lat = 1 + self.tm["l"] + self.tm["s"]
        st["infl"] = st["infl"].at[(t + lat) % L, fab.NQ:].set(
            jnp.where(anyf[:, None], pay, 0))
        hit = emit[:, None] & (lane == (seq % W)[:, None])
        sent = st["sent"]
        st["sent"] = sent.at[:, :NF].set(jnp.stack([
            jnp.where(hit, 1, s0), jnp.where(hit, seq[:, None], s1),
            jnp.where(hit, t, sent[2, :NF])]))
        st["next_seq"] = nseq + (emit & ~has_retx).astype(I32)
        st["m.n_retx"] = st["m.n_retx"] + jnp.sum((emit & has_retx)
                                                  .astype(I32))

    # ---- whole runs -----------------------------------------------------

    def run(self, c: dict, salt) -> dict:
        """Step from tick 0 until every flow is done or the budget ends.
        The constants ``c`` (``self.c``, or a sweep point's) are an argument:
        closed over, XLA would fold them into the float arithmetic and
        round differently."""
        def cond(st):
            return (st["now"] < self.max_ticks) & ~jnp.all(st["done"])
        return jax.lax.while_loop(cond, lambda st: self.tick(c, st),
                                  self.init(c, salt))

    def jit_run(self):
        """``run`` compiled once for this deployment's shapes:
        ``(consts, salt) -> final state``."""
        return jax.jit(self.run)
