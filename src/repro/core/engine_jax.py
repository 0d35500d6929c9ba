"""Jitted packing engine (beyond-paper optimization): one fused multi-type
pass over *task classes* instead of tasks.

Algorithm 1's inner argmax is reformulated incrementally so each add step is
cheap:

  TNRP(T ∪ {c}) = cur − Σ_m jobrp_m·tput_m·(1 − P[w_m, w_c])
                      + rp_c − (1 − Π_m P[w_c, w_m])·jobrp_c

The member sum collapses onto per-workload aggregates agg_w = Σ_{m:w_m=w}
jobrp_m·tput_m (updated in O(W) per add, queried via agg·P), and candidate
throughputs are maintained as running log-products — exactly the formulation
the per-type engine used, with two fleet-scale upgrades:

* **Class collapse.**  Tasks with identical (workload, RP, job-RP, demand)
  are interchangeable to Algorithm 1, so the argmax runs over the C ≤ ~tens
  of distinct *classes* with multiplicity counts, not the T tasks — each
  greedy step is O(C·W + W²) regardless of fleet size.  When the pairwise
  matrix is all-ones (interference-oblivious packs) classes additionally
  merge across workloads with equal price/demand rows.
* **Single jitted multi-type pass.**  The whole descending-cost type loop —
  fills, cost-efficiency acceptance, per-region instance budgets — runs as
  nested ``lax.while_loop``s inside one ``lax.fori_loop`` in a single jitted
  call with donated count/budget buffers; Python only expands the returned
  fill records back to task rows.
* **Fill replication.**  A greedy fill whose argmax was unique at every step
  replays identically while every used class retains enough tasks, so it is
  emitted once with a replication factor ``rep = min_c ⌊count_c/used_c⌋``
  (capped by the region budget) instead of being recomputed per instance.
  Fills that broke an exact cross-class score tie are not replicated
  (``rep = 1``): the tie is resolved by the *current lowest task row* of
  each tied class — the same first-maximal-row rule the numpy engine uses —
  and that row pointer advances between fills.

Together the pass is pick-for-pick identical to the per-type task-level
engine (and tie-break-compatible with the numpy engine) while planning
10⁵–10⁶-task fleets in far less than numpy needs for 10⁴
(``benchmarks/bench_micro.py scaling``).

Single-task TNRP (tput·RP) is the multi-task formula with jobrp ≡ rp, so one
code path serves both.  Unlike the earlier per-type engine, ``pack_jax`` now
accepts ``type_mask`` and ``region_budget`` with the same contract as the
numpy/python packers (budget consumption is written back in place), so every
Full/Partial Reconfiguration path — masked, region-capped and overflow
re-packs included — can run jitted.

All floating-point state is kept in the canonical JAX float dtype
(float32 by default, float64 under ``jax_enable_x64``) with accumulators
built explicitly from that dtype, so enabling x64 changes precision, not
semantics.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import profiler as _prof
from .catalog import Catalog

_EPS = 1e-9
_NEG = -1e30
_BIG_I = np.int32(np.iinfo(np.int32).max // 2)  # headroom for decrements


def _collapse_classes(workloads: np.ndarray, rp: np.ndarray, jr: np.ndarray,
                      demand: np.ndarray, merge_workloads: bool):
    """Group interchangeable tasks into classes.

    Returns ``(inv, cw, crp, cjr, cdemand, counts)`` where ``inv`` maps each
    task row to its class.  Fast path: when price/demand vectors are constant
    per workload (the common case — demands come from the workload profile
    and RP is a function of demand), classes are just the workloads present
    (further merged across workloads when ``merge_workloads`` — i.e. the
    pairwise matrix is all-ones and workload identity is inert).
    """
    T = workloads.shape[0]
    d2 = np.ascontiguousarray(demand.reshape(T, -1), dtype=np.float64)
    cols = np.column_stack([rp.astype(np.float64), jr.astype(np.float64), d2])
    order = np.argsort(workloads, kind="stable")
    ws = workloads[order]
    starts = np.nonzero(np.concatenate([[True], ws[1:] != ws[:-1]]))[0]
    grouped = cols[order]
    lo = np.minimum.reduceat(grouped, starts, axis=0)
    hi = np.maximum.reduceat(grouped, starts, axis=0)
    if np.array_equal(lo, hi):
        present = ws[starts]  # distinct workloads, ascending
        remap = np.zeros(int(workloads.max()) + 1, dtype=np.int64)
        remap[present] = np.arange(present.size)
        inv = remap[workloads]
        keys, cw = lo, present.astype(np.int64)
        if merge_workloads:
            _, uidx, uinv = np.unique(keys, axis=0, return_index=True,
                                      return_inverse=True)
            inv = uinv.reshape(-1)[inv]
            cw = cw[uidx]
            keys = keys[uidx]
    else:  # per-workload keys vary (e.g. per-job RP sums): full row unique
        full = cols if merge_workloads else np.column_stack(
            [workloads.astype(np.float64), cols])
        _, uidx, inv = np.unique(full, axis=0, return_index=True,
                                 return_inverse=True)
        inv = inv.reshape(-1)
        cw = workloads[uidx].astype(np.int64)
        keys = cols[uidx]
    counts = np.bincount(inv).astype(np.int32)
    crp, cjr = keys[:, 0], keys[:, 1]
    cdemand = keys[:, 2:].reshape(len(counts), demand.shape[1],
                                  demand.shape[2])
    return inv, cw, crp, cjr, cdemand, counts


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


@functools.partial(jax.jit, static_argnames=("max_fills",),
                   donate_argnums=(12,))
def _pack_all_types(cdemand, cw, crp, cjr, counts0, rows_pad, P, logP,
                    costs, caps, fams, rids, budget, *, max_fills: int):
    """One fused pass over every (masked-in) type in descending-cost order.

    Shapes: cdemand (F,R,C) · cw/crp/cjr/counts0 (C,) · rows_pad (C,M) ·
    P/logP (W,W) · costs/fams/rids (K,) · caps (K,R) · budget (NR,).
    Returns the final budget plus ``max_fills``-bounded fill records
    (type position, replication, per-class composition), an overflow
    flag (caller retries with a larger buffer — record count is bounded by
    the task count, so the retry always terminates) and two counts: the
    greedy steps evaluated (each adds a task or ends its fill) and the
    fills attempted.

    Every per-class operand of a greedy step is dense over the class axis,
    which runs along the vector lanes: the demands are class-minor, the
    running log-throughput update is a row of the class-major table
    ``logPc``, and the tie key (each class's current lowest task row) is
    carried across steps and fills instead of gathered from ``rows_pad``.
    """
    C = cw.shape[0]
    M = rows_pad.shape[1]
    W = P.shape[0]
    dt = crp.dtype
    arange_c = jnp.arange(C)
    # complement interference matrix: the multi-task member penalty is
    # Σ_w agg_w·(1 − P[w, c]) = (agg @ Q)[c], which is *exactly* zero when
    # interference is off (P ≡ 1) instead of carrying the reduction-order
    # residual of agg.sum() − (agg @ P)[c]
    Q = 1.0 - P
    # logPc[w, c] = log P[w_c, w]: adding a task of workload w multiplies
    # every candidate class's throughput by P[w_c, w]
    logPc = logP.T[:, cw]
    # of_w[w, c] = (w_c == w): a class's entry of a per-workload vector is
    # the sum down its column of this mask, exact (one term is not zero)
    of_w = jnp.arange(W)[:, None] == cw[None, :]
    # break-even acceptance: fills on a task's RP type sum exactly to the
    # instance cost under the catalog's linear pricing, so the gate needs a
    # tolerance matched to the accumulator dtype — f32 greedy sums drift
    # ~n·eps·cost over an n-task fill; under jax_enable_x64 the relative
    # term collapses below the absolute 1e-9 epsilon, matching numpy.  The
    # fit test gets the same slack on the remaining capacity: decimal
    # demands (e.g. 173.6 GB) leave f32 residuals below an exact fit
    rtol = dt.type(256 * jnp.finfo(dt).eps)

    def row_at(c, j):
        """rows_pad[c, min(j, M − 1)] for scalar c, j."""
        return jax.lax.dynamic_slice(
            rows_pad, (c, jnp.minimum(j, M - 1)), (1, 1))[0, 0]

    def fill_one(counts, d, cap0, rowkey, picks):
        """Greedy-fill one fresh instance; returns (used, tnrp, had_tie,
        rowkey, picks) with the tie key advanced past the used rows."""
        fit_tol = _EPS + rtol * cap0

        def cond(s):
            return ~s[-1]

        def body(s):
            used, capr, logtput, agg, cur, tie, rowkey, picks, _ = s
            feas = ((counts - used) > 0) & jnp.all(
                d <= (capr + fit_tol)[:, None], axis=0)
            cand_tput = jnp.exp(logtput)
            # full f32 passes: at default precision the TPU's MXU rounds
            # through bf16, which can flip the argmax under interference
            qvec = jnp.matmul(agg, Q, precision=jax.lax.Precision.HIGHEST)
            qvec_c = jnp.where(of_w, qvec[:, None], dt.type(0)).sum(axis=0)
            score = cur - qvec_c + crp - (1.0 - cand_tput) * cjr
            masked = jnp.where(feas, score, dt.type(_NEG))
            mx = masked.max()
            ok = feas.any() & (mx >= cur - _EPS)
            at_max = feas & (masked == mx)
            crosstie = at_max.sum() > 1
            # lowest current task row among the tied = numpy's first max
            best = jnp.argmin(jnp.where(at_max, rowkey, _BIG_I))
            best = best.astype(jnp.int32)
            hit = arange_c == best
            wb = cw[best]
            tput_b = cand_tput[best]
            ptr_b = counts0[best] - counts[best] + used[best]
            n_used = jnp.where(hit, used + 1, used)
            n_capr = capr - d[:, best]
            n_logtput = logtput + logPc[wb]
            n_agg = (agg * P[:, wb]).at[wb].add(cjr[best] * tput_b)
            n_rowkey = jnp.where(hit, row_at(best, ptr_b + 1), rowkey)
            used = jnp.where(ok, n_used, used)
            capr = jnp.where(ok, n_capr, capr)
            logtput = jnp.where(ok, n_logtput, logtput)
            agg = jnp.where(ok, n_agg, agg)
            cur = jnp.where(ok, mx, cur)
            tie = tie | (crosstie & ok)
            rowkey = jnp.where(ok, n_rowkey, rowkey)
            return (used, capr, logtput, agg, cur, tie, rowkey, picks + 1,
                    ~ok)

        init = (jnp.zeros(C, jnp.int32), cap0, jnp.zeros(C, dt),
                jnp.zeros(W, dt), jnp.zeros((), dt), jnp.asarray(False),
                rowkey, picks, jnp.asarray(False))
        used, _, _, _, cur, tie, rowkey, picks, _ = jax.lax.while_loop(
            cond, body, init)
        return used, cur, tie, rowkey, picks

    def type_body(t, st):
        cost = costs[t]
        cap0 = caps[t]
        rid = rids[t]
        d = cdemand[fams[t]]  # (R, C) on this family

        def fcond(s):
            return s[-1]

        def fbody(s):
            (counts, budget, rowkey, rt, rr, rc, n_rec, ovf, picks, fills,
             _) = s
            used, cur, had_tie, filled_key, picks = fill_one(
                counts, d, cap0, rowkey, picks)
            accept = ((used.sum() > 0)
                      & (cur >= cost - _EPS - rtol * cost)
                      & (budget[rid] > 0))
            rep_c = jnp.where(used > 0, counts // jnp.maximum(used, 1),
                              _BIG_I)
            rep = jnp.minimum(rep_c.min(), budget[rid])
            rep = jnp.where(had_tie, 1, rep).astype(jnp.int32)
            can = n_rec < max_fills
            idx = jnp.minimum(n_rec, max_fills - 1)
            wr = accept & can
            rt = rt.at[idx].set(jnp.where(wr, t.astype(jnp.int32), rt[idx]))
            rr = rr.at[idx].set(jnp.where(wr, rep, rr[idx]))
            rc = rc.at[idx].set(jnp.where(wr, used, rc[idx]))
            n_rec = n_rec + jnp.where(accept, 1, 0).astype(jnp.int32)
            ovf = ovf | (accept & ~can)
            counts = jnp.where(accept, counts - rep * used, counts)
            budget = jnp.where(accept, budget.at[rid].add(-rep), budget)
            # a rejected fill leaves every pointer where it was; a
            # replicated one moves them past rep copies, so its key is
            # read afresh: each class's lowest row not yet placed
            rowkey = jax.lax.cond(
                accept & (rep > 1),
                lambda: rows_pad[arange_c,
                                 jnp.minimum(counts0 - counts, M - 1)],
                lambda: jnp.where(accept, filled_key, rowkey))
            go = accept & (counts > 0).any()
            return (counts, budget, rowkey, rt, rr, rc, n_rec, ovf, picks,
                    fills + 1, go)

        counts = st[0]
        init = st + ((counts > 0).any(),)
        return jax.lax.while_loop(fcond, fbody, init)[:-1]

    zero = jnp.zeros((), jnp.int32)
    st = (counts0, budget, rows_pad[:, 0],
          jnp.full((max_fills,), -1, jnp.int32),
          jnp.zeros((max_fills,), jnp.int32),
          jnp.zeros((max_fills, C), jnp.int32), zero, jnp.asarray(False),
          zero, zero)
    st = jax.lax.fori_loop(0, costs.shape[0], type_body, st)
    _, budget, _, rec_type, rec_rep, rec_comp, n_rec, overflow, picks, \
        fills = st
    return budget, rec_type, rec_rep, rec_comp, n_rec, overflow, picks, fills


def pack_jax(demand_by_family: np.ndarray, workloads: np.ndarray,
             rp: np.ndarray, job_rp: Optional[np.ndarray], catalog: Catalog,
             pairwise: np.ndarray,
             type_mask: Optional[np.ndarray] = None,
             region_budget: Optional[np.ndarray] = None
             ) -> List[Tuple[int, List[int]]]:
    """Engine entry point (same contract as the numpy/python engines,
    including in-place ``region_budget`` consumption)."""
    T = demand_by_family.shape[0]
    if T == 0:
        return []
    with _prof.span("pack.prepare") as sp:
        jr = rp if job_rp is None else job_rp  # single-task == jobrp ≡ rp
        dt = jax.dtypes.canonicalize_dtype(np.float64)
        merge = bool(np.all(pairwise == 1.0))
        inv, cw, crp, cjr, cdemand, counts = _collapse_classes(
            np.asarray(workloads), np.asarray(rp), np.asarray(jr),
            np.asarray(demand_by_family), merge)
        C = counts.size
        order_rows = np.argsort(inv, kind="stable")  # ascending rows/class
        starts = np.concatenate([[0], np.cumsum(counts)])

        # pad class axis / row queues to power-of-two buckets so jit shapes
        # (and compilations) stay bounded as fleet composition changes round
        # to round
        c_pad = _pow2(C, 4)
        m_cap = _pow2(int(counts.max()), 8)
        rows_pad = np.full((c_pad, m_cap), T, np.int32)
        for c in range(C):
            rows_pad[c, :counts[c]] = order_rows[starts[c]:starts[c + 1]]
        pad = c_pad - C
        counts_p = np.concatenate([counts, np.zeros(pad, np.int32)])
        cw_p = np.concatenate([cw, np.zeros(pad, np.int64)]).astype(np.int32)
        crp_p = np.concatenate([crp, np.zeros(pad)]).astype(dt)
        cjr_p = np.concatenate([cjr, np.zeros(pad)]).astype(dt)
        cdem_p = np.concatenate(
            [cdemand, np.zeros((pad,) + cdemand.shape[1:])]).astype(dt)
        cdem_p = np.ascontiguousarray(cdem_p.transpose(1, 2, 0))  # (F,R,C)

        ks = [k for k in catalog.order_desc.tolist()
              if type_mask is None or bool(np.asarray(type_mask)[k])]
        if not ks:
            return []
        costs = catalog.costs[ks].astype(dt)
        caps = catalog.capacities[ks].astype(dt)
        fams = catalog.family_ids[ks].astype(np.int32)
        if region_budget is not None:
            rids = catalog.region_ids[ks].astype(np.int32)
            budget0 = np.minimum(region_budget, _BIG_I).astype(np.int32)
        else:
            rids = np.zeros(len(ks), np.int32)
            budget0 = np.array([_BIG_I], np.int32)

        P = jnp.asarray(pairwise, dt)
        logP = jnp.log(jnp.maximum(P, 1e-9))
        max_fills = _pow2(max(256, T // 2 + 8), 256)
    if sp is not None:
        sp.tags["classes"] = C
    cache_size = getattr(_pack_all_types, "_cache_size", lambda: -1)
    while True:  # record count ≤ T, so doubling always terminates
        # the jit cache is read only for the span's stage tag
        n_cached = cache_size() if _prof.active() is not None else -1
        # the module-level span hook is a shared nullcontext (sp is None)
        # unless a profiler was activated; the bool(overflow) host sync sits
        # inside the span so device time is part of the measurement
        with _prof.span("jax_pack") as sp:
            (budget_out, rec_type, rec_rep, rec_comp, n_rec, overflow,
             picks, fills) = _pack_all_types(
                jnp.asarray(cdem_p), jnp.asarray(cw_p), jnp.asarray(crp_p),
                jnp.asarray(cjr_p), jnp.asarray(counts_p),
                jnp.asarray(rows_pad), P, logP, jnp.asarray(costs),
                jnp.asarray(caps), jnp.asarray(fams), jnp.asarray(rids),
                jnp.asarray(budget0), max_fills=max_fills)
            overflowed = bool(overflow)
        if sp is not None:  # jit-cache growth == this call compiled
            sp.tags["stage"] = ("compile" if cache_size() > n_cached
                                else "execute")
            sp.tags["max_fills"] = max_fills
            sp.tags["n_tasks"] = T
            sp.tags["picks"] = int(picks)
            sp.tags["fills"] = int(fills)
        if not overflowed:
            break
        max_fills *= 2

    with _prof.span("pack.readback") as sp:
        nrec = int(n_rec)
        rt = np.asarray(rec_type[:nrec])
        rr = np.asarray(rec_rep[:nrec])
        rc = np.asarray(rec_comp[:nrec])
        ptr = starts[:-1].copy()
        out: List[Tuple[int, List[int]]] = []
        for i in range(nrec):
            k = ks[int(rt[i])]
            rep = int(rr[i])
            comp = rc[i]
            cls = np.nonzero(comp[:C])[0]
            chunks = []
            for c in cls:
                n = int(comp[c]) * rep
                chunks.append(order_rows[ptr[c]:ptr[c] + n]
                              .reshape(rep, int(comp[c])))
                ptr[c] += n
            allrows = np.concatenate(chunks, axis=1)
            for j in range(rep):
                out.append((k, allrows[j].tolist()))
        if region_budget is not None:
            consumed = budget0.astype(np.int64) - np.asarray(budget_out,
                                                             dtype=np.int64)
            region_budget -= consumed  # in place: callers track the rest
    if sp is not None:
        sp.tags["records"] = nrec
    return out
