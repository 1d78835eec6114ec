"""Fused fast path for the canonical CRUSH rules on two-level maps.

The generic batched mapper (mapper_jax) re-draws the whole batch every retry
ladder iteration and pads every bucket row to the global max bucket size.  For
the rule shapes that carry ~all real placement traffic —

    take root
    chooseleaf firstn N type-t     (replicated pools; mapper.c:460-648)
    emit
and
    take root
    choose firstn N osd            (flat maps)
    emit

over a *uniform two-level* straw2 hierarchy (root -> type-t buckets ->
devices), a better device schedule exists because the retry ladder's r values
are shared across replicas: replica ``rep`` draws with r = rep + ftotal, so
the whole ladder for all reps only ever consumes root/leaf winners at
r in [0, numrep + max_ftotal).  The fast path therefore:

  1. precomputes straw2 winners for a block of r values — a fori_loop
     producing one r column per step (root (N, H) draw -> winner; that
     host's item/weight rows, padded only to the max *leaf* size, -> (N, S)
     leaf draw -> device + its is_out verdict);
  2. consumes them with numrep cheap masked while_loops whose bodies are
     (N,)-sized gathers and compares — no redraws, and reps 1..n-1 reuse the
     winners rep 0 already paid for;
  3. if any lane's ftotal walks past the precomputed block (rare: needs many
     consecutive collisions/rejections), a lax.cond re-runs the same
     computation with the full r range R = tries + numrep, which by
     construction cannot overflow — bit-exactness is unconditional, the big
     recompute just never happens on healthy maps.

(A weight-class decomposition — draws are monotone in the 16-bit hash, so
only the max-u item per distinct weight can win — was evaluated and rejected:
truncated-quotient ties between items are common at realistic bucket weights
(quotient spacing ~ crush_ln slope / w approaches 1 for host-sized w), so an
exactness fallback triggers on virtually every bulk call.  The argmax over
full per-item draws handles ties for free.)

Bit-exactness: validated against the scalar oracle (crush.mapper_ref) in
tests/test_mapper_jax.py::test_fastpath_* across skewed weights, reweights,
out OSDs, uneven host sizes, and forced-fallback configurations.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ops.crush_kernel import is_out
from ceph_tpu.ops.straw2_u32 import (
    _ln_f32_error_bound, magic_tables, straw2_choose_index_approx)

from .types import (
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSELEAF_FIRSTN,
    RULE_EMIT,
    RULE_SET_CHOOSE_TRIES,
    RULE_SET_CHOOSELEAF_TRIES,
    RULE_TAKE,
    CrushMap,
)

NONE = jnp.int32(CRUSH_ITEM_NONE)

#: extra r-values beyond numrep precomputed in the first block.  6 covers
#: every lane on healthy maps (ftotal beyond 6 needs seven consecutive
#: collision/reject draws); the overflow cond recomputes with the full
#: range when it ever does not, so this is a latency knob, not a
#: correctness one.
DEFAULT_BLOCK = 6


@dataclass
class FastRule:
    """Host-side description of a fast-path-eligible rule."""

    kind: str                 # "chooseleaf" | "choose_flat"
    numrep_arg: int           # step arg1 (0 -> result_max)
    tries: int                # choose_total_tries + 1 (or SET override)
    vary_r: int
    root_ids: np.ndarray      # (H,) root bucket items
    root_w: np.ndarray        # (H,) int64 16.16 weights
    leaf_ids: np.ndarray | None   # (H, S) device ids, row per root item
    leaf_w: np.ndarray | None     # (H, S) int64, 0-padded
    max_devices: int


def detect(m: CrushMap, ruleno: int) -> FastRule | None:
    """Return a FastRule if ``ruleno`` on map ``m`` fits the fused kernel."""
    t = m.tunables
    if (t.choose_local_tries or t.choose_local_fallback_tries
            or t.chooseleaf_stable != 1):
        return None
    rule = m.rules[ruleno]
    if rule is None:
        return None
    tries = t.choose_total_tries + 1
    core: list = []
    for step in rule.steps:
        if step.op == RULE_SET_CHOOSE_TRIES:
            if step.arg1 > 0:
                tries = step.arg1
        elif step.op == RULE_SET_CHOOSELEAF_TRIES:
            if step.arg1 > 0 and step.arg1 != 1:
                return None  # leaf retry loop not fused
        else:
            core.append(step)
    if len(core) != 3:
        return None
    take, choose, emit = core
    if take.op != RULE_TAKE or emit.op != RULE_EMIT:
        return None
    root = m.bucket(take.arg1)
    if root is None or root.alg != CRUSH_BUCKET_STRAW2 or root.size == 0:
        return None
    if root.size > 1024:
        return None  # (N, R, H) blocks would dwarf the iterative cost
    root_ids = np.asarray(root.items, dtype=np.int32)
    root_w = np.asarray(root.item_weights, dtype=np.int64)

    if choose.op == RULE_CHOOSE_FIRSTN and choose.arg2 == 0:
        # flat: every root item is a device
        if any(i < 0 or i >= m.max_devices for i in root.items):
            return None
        return FastRule(
            kind="choose_flat", numrep_arg=choose.arg1, tries=tries,
            vary_r=t.chooseleaf_vary_r, root_ids=root_ids, root_w=root_w,
            leaf_ids=None, leaf_w=None, max_devices=m.max_devices)

    if choose.op != RULE_CHOOSELEAF_FIRSTN:
        return None
    if not t.chooseleaf_descend_once:
        # without descend_once the leaf recursion retries inside the host
        # (recurse_tries = choose_tries, mapper.c:1041-1046); the fused
        # kernel only models the single-attempt (descend_once) semantics
        return None
    want_type = choose.arg2
    hosts = []
    for item in root.items:
        h = m.bucket(item)
        if (h is None or h.alg != CRUSH_BUCKET_STRAW2
                or h.type != want_type or h.size == 0):
            return None
        if any(i < 0 or i >= m.max_devices for i in h.items):
            return None
        hosts.append(h)
    s_max = max(h.size for h in hosts)
    leaf_ids = np.zeros((len(hosts), s_max), dtype=np.int32)
    leaf_w = np.zeros((len(hosts), s_max), dtype=np.int64)
    for row, h in enumerate(hosts):
        leaf_ids[row, :h.size] = h.items
        leaf_w[row, :h.size] = h.item_weights
    return FastRule(
        kind="chooseleaf", numrep_arg=choose.arg1, tries=tries,
        vary_r=t.chooseleaf_vary_r, root_ids=root_ids, root_w=root_w,
        leaf_ids=leaf_ids, leaf_w=leaf_w, max_devices=m.max_devices)


# ---------------------------------------------------------------------------
# device kernels
# ---------------------------------------------------------------------------

def _draw_argmax(x, ids, weights, r, magic, off):
    """Straw2 winner position for one r value across the batch.

    x (N,) uint32; ids (S,) shared or (N, S) per-lane rows; weights /
    magic / off broadcastable to ids; r scalar uint32.  Returns (N,)
    positions.  Runs the u32 magic-division kernel (ops.straw2_u32) —
    bit-exact against the s64 kernel by exhaustive validation — whose
    argmin takes the first minimum, exactly the strict-``>`` scan of
    bucket_straw2_choose (mapper.c:374-380): truncation ties resolve to
    the lowest index for free.
    """
    idb = ids[None, :] if ids.ndim == 1 else ids
    wb = jnp.broadcast_to(
        weights[None, :] if weights.ndim == 1 else weights, idb.shape)
    mb = jnp.broadcast_to(
        magic[None, :, :] if magic.ndim == 2 else magic, (*idb.shape, 5))
    ob = jnp.broadcast_to(
        off[None, :] if off.ndim == 1 else off, idb.shape)
    return straw2_choose_index_approx(x, idb, r, wb, mb, ob)


def _consume(host_win, leaf_win, leaf_bad, numrep, tries, R, n):
    """Walk the firstn ladder over precomputed winners.

    host_win (N, R) int32: first-level item chosen at r (host id, or the
    device itself for flat rules).  leaf_win (N, R) int32: device at r.
    leaf_bad (N, R) bool: device rejected (is_out).  Returns
    (out_host, out_leaf, overflow): (N, numrep) selections with NONE holes
    and a per-lane flag for ftotal walking past R.
    """
    out_h = jnp.full((n, numrep), NONE, dtype=jnp.int32)
    out_l = jnp.full((n, numrep), NONE, dtype=jnp.int32)
    overflow = jnp.zeros((n,), dtype=bool)

    for rep in range(numrep):
        def cond(s):
            return jnp.any(s[3])

        def body(s, rep=rep, out_h=out_h, out_l=out_l):
            sel_h, sel_l, ft, act, ovf = s
            r = rep + ft
            within = r < R
            ridx = jnp.minimum(r, R - 1)[:, None]
            hb = jnp.take_along_axis(host_win, ridx, 1)[:, 0]
            lf = jnp.take_along_axis(leaf_win, ridx, 1)[:, 0]
            bad_l = jnp.take_along_axis(leaf_bad, ridx, 1)[:, 0]
            coll_h = jnp.any(out_h == hb[:, None], axis=1)
            coll_l = jnp.any(out_l == lf[:, None], axis=1)
            bad = coll_h | coll_l | bad_l
            place = act & within & ~bad
            sel_h = jnp.where(place, hb, sel_h)
            sel_l = jnp.where(place, lf, sel_l)
            ft = jnp.where(act & within & bad, ft + 1, ft)
            ovf = ovf | (act & ~within)
            act = act & within & bad & (ft < tries)
            return sel_h, sel_l, ft, act, ovf

        sel0 = jnp.full((n,), NONE, dtype=jnp.int32)
        sel_h, sel_l, _, _, overflow = jax.lax.while_loop(
            cond, body,
            (sel0, sel0, jnp.zeros((n,), jnp.int32),
             jnp.ones((n,), bool), overflow))
        out_h = out_h.at[:, rep].set(sel_h)
        out_l = out_l.at[:, rep].set(sel_l)
    return out_h, out_l, overflow


def _compact_rows(rows):
    order = jnp.argsort(rows == NONE, axis=1)
    return jnp.take_along_axis(rows, order, axis=1)


class FastMapper:
    """Compiled fast path for one (map, rule)."""

    def __init__(self, fr: FastRule):
        self.fr = fr
        _ln_f32_error_bound()   # measure eagerly: must be concrete by
        self.root_ids = jnp.asarray(fr.root_ids)   # the time jit traces
        self.root_w = jnp.asarray(fr.root_w)
        rm, ro = magic_tables(fr.root_w)
        self.root_magic = jnp.asarray(rm)
        self.root_off = jnp.asarray(ro)
        if fr.leaf_ids is not None:
            self.leaf_ids = jnp.asarray(fr.leaf_ids)
            self.leaf_w = jnp.asarray(fr.leaf_w)
            lm, lo = magic_tables(fr.leaf_w)
            self.leaf_magic = jnp.asarray(lm)
            self.leaf_off = jnp.asarray(lo)
        # the fused Pallas column kernels (2.5x the XLA path on this
        # backend); TPU-only — the CPU mesh tests keep the XLA path.
        # Mesh-sharded batches reach these kernels through the
        # shard_map wrapper in BatchMapper._fast_sharded_fn (a
        # pallas_call is an opaque custom call GSPMD cannot split, so
        # the batch splits BEFORE the kernel; run() itself is
        # row-independent along x by the oracle-equivalence contract).
        # The gate honors jax.default_device(<tpu>) too: a multi-
        # platform process (cpu default + tpu reachable) running under
        # that context IS on the tpu even though default_backend()
        # still says cpu
        self._pallas = None
        _dd = getattr(jax.config, "jax_default_device", None)
        if _dd is not None:
            # jax.default_device accepts a Device OR a platform string
            on_tpu = getattr(_dd, "platform", str(_dd)) == "tpu"
        else:
            on_tpu = jax.default_backend() == "tpu"
        if on_tpu:
            try:
                from ceph_tpu.ops.pallas_straw2 import PallasColumns
            except ImportError:   # pragma: no cover
                PallasColumns = None
            if PallasColumns is not None:
                # construction failures must surface, not silently
                # degrade to the slower XLA path
                self._pallas = PallasColumns(fr)

    def _winners(self, xs, reweight, R: int):
        """host_win/leaf_win/leaf_bad for r in [0, R): a fori_loop producing
        one r column per step (bounds the (N, H) ln-matmul intermediates to a
        single r; an unrolled R-wide block OOMs HBM at bulk batch sizes)."""
        fr = self.fr
        n = xs.shape[0]
        hw0 = jnp.full((n, R), NONE, dtype=jnp.int32)
        lw0 = jnp.full((n, R), NONE, dtype=jnp.int32)
        lb0 = jnp.zeros((n, R), dtype=bool)

        def body(i, bufs):
            hw, lw, lb = bufs
            r = i.astype(jnp.uint32)
            pos = _draw_argmax(xs, self.root_ids, self.root_w, r,
                               self.root_magic, self.root_off)
            first = self.root_ids[pos]                         # (N,)
            if fr.kind == "choose_flat":
                leaf = first
            else:
                # r_leaf = vary_r ? r >> (vary_r-1) : 0 (mapper.c:578)
                if fr.vary_r:
                    r_leaf = r >> jnp.uint32(fr.vary_r - 1)
                else:
                    r_leaf = jnp.uint32(0)
                ids = self.leaf_ids[pos]                       # (N, S)
                w = self.leaf_w[pos]                           # (N, S)
                lpos = _draw_argmax(xs, ids, w, r_leaf,
                                    self.leaf_magic[pos],
                                    self.leaf_off[pos])
                leaf = jnp.take_along_axis(ids, lpos[:, None], 1)[:, 0]
            bad = is_out(reweight, leaf, xs)
            hw = jax.lax.dynamic_update_slice(hw, first[:, None], (0, i))
            lw = jax.lax.dynamic_update_slice(lw, leaf[:, None], (0, i))
            lb = jax.lax.dynamic_update_slice(lb, bad[:, None], (0, i))
            return hw, lw, lb

        return jax.lax.fori_loop(0, R, body, (hw0, lw0, lb0))

    def _winners_cols(self, xs, reweight, R: int):
        """(host_win, leaf_win, leaf_bad) in the native (R, n_padded)
        column layout of the Pallas kernels (no transposes).

        Root columns go through the fused approx-filter kernel when the
        R columns' candidates fit one lane block; its certificate flag
        (any column with more than K items inside the measured f32
        error band) falls the whole batch back to the exact column
        kernel, so bit-exactness is unconditional."""
        pc = self._pallas
        from ceph_tpu.ops.pallas_straw2 import _KPACK
        if R * _KPACK <= 128 and 512 <= pc.S_root <= 1024:
            # the approx filter narrows each column from S items to K
            # candidates — a win only when S spans many slabs (big flat
            # buckets); at host-count-sized roots the packing machinery
            # costs more than the exact pipeline it saves (measured).
            # Upper bound: the extractor packs item positions into 10
            # bits (pallas_straw2._extract_candidates), so past 1024
            # items the certificate would fire on every batch and the
            # filter pass would be pure overhead
            pos, ids, ovf = pc.froot_columns(xs, reweight, R)
            pos, ids = jax.lax.cond(
                jnp.any(ovf != 0),
                lambda _: pc.root_columns(xs, reweight, R),
                lambda _: (pos, ids), None)
        else:
            pos, ids = pc.root_columns(xs, reweight, R)
        # the winner columns come back padded to the kernel block quantum
        n_pad = ids.shape[1]
        xs_pad = jnp.concatenate(
            [xs, jnp.zeros((n_pad - xs.shape[0],), dtype=xs.dtype)]) \
            if n_pad > xs.shape[0] else xs
        if self.fr.kind == "choose_flat":
            # is_out runs OUTSIDE the kernels: it is elementwise in
            # (winner, x), one cheap XLA op over the columns — and the
            # in-kernel variant hit a Mosaic miscompile (hash32_2 fed
            # from the winner gather/sum pipeline went wrong for ~0.03%
            # of lanes, compiled mode only; caught by TPU-vs-XLA
            # cross-validation in round 3)
            bad = is_out(reweight, ids, xs_pad[None, :])
            return ids, ids, bad
        lid = self._pallas.leaf_columns(xs, pos, R)
        lbad = is_out(reweight, lid, xs_pad[None, :])
        return ids, lid, lbad

    #: minimum batch for the two-stage schedule; below it one pass at R0
    #: is cheaper than the compaction plumbing
    TWO_STAGE_MIN = 32768
    #: stage-2 capacity: lanes whose ladder outran the stage-1 columns.
    #: At realistic reject/collision rates the expected count is a few
    #: hundred per 64Ki (p ~ fail^2 per lane); 4096 makes the capacity
    #: overflow a tail-of-tail event, and the guard recomputes the whole
    #: batch when it ever fires, so it costs latency, never correctness.
    STAGE2_CAP = 4096
    #: ... and never less than one lane in STAGE2_SHARE of the batch:
    #: the count grows with the batch, and a fixed capacity turns the
    #: guard into the rule (on the 10,000-OSD map 2.9% of lanes overflow
    #: stage 1: 1,900 of 64Ki, 31,000 of 1Mi).  At 64Ki lanes one in 16
    #: is the 4096 above.
    STAGE2_SHARE = 16

    def _run_pallas(self, xs, reweight, result_max, numrep, R0, Rf):
        """Winner columns and the consume ladder both on-device in their
        native (R, N) layout — no transposes, no XLA while_loops.

        Bulk batches run a two-stage schedule: stage 1 computes only
        numrep+1 columns for every lane (covers lanes whose firstn
        ladder saw at most one failure in the last replica — ~99% at
        realistic maps), then gathers the overflowing lanes into one
        compact STAGE2_CAP batch that gets the full R0 treatment.  The
        placement for a given x is identical either way — the ladder is
        deterministic in (x, columns) — so this is pure scheduling, the
        oracle-equivalence property is untouched."""
        from ceph_tpu.ops.pallas_straw2 import consume_columns
        fr = self.fr
        n = xs.shape[0]
        interp = self._pallas.interpret

        def attempt(xv, R):
            m = xv.shape[0]
            hw, lw, lb = self._winners_cols(xv, reweight, R)
            oh, ol, ovf = consume_columns(
                hw, lw, lb, numrep=numrep, tries=fr.tries, interpret=interp)
            return oh[:, :m], ol[:, :m], ovf[:m]

        def attempt_full(xv, R):
            oh, ol, ovf = attempt(xv, R)
            return jax.lax.cond(
                jnp.any(ovf != 0),
                lambda _: attempt(xv, Rf)[:2],
                lambda _: (oh, ol), None)

        R1 = numrep + 1
        if n < self.TWO_STAGE_MIN or R1 >= R0:
            out_h, out_l = attempt_full(xs, R0)
        else:
            oh1, ol1, ovf1 = attempt(xs, R1)
            cap = max(self.STAGE2_CAP, n // self.STAGE2_SHARE)
            need = ovf1 != 0
            # overflowing lanes first, stable, then fillers
            order = jnp.argsort(jnp.where(need, 0, 1), stable=True)
            idx_c = order[:cap]
            xs2 = xs[idx_c]

            def merged(_):
                oh2, ol2 = attempt_full(xs2, R0)
                sel = need[idx_c][None, :]
                oh = oh1.at[:, idx_c].set(
                    jnp.where(sel, oh2, oh1[:, idx_c]))
                ol = ol1.at[:, idx_c].set(
                    jnp.where(sel, ol2, ol1[:, idx_c]))
                return oh, ol

            out_h, out_l = jax.lax.cond(
                jnp.sum(need) > cap,
                lambda _: attempt_full(xs, R0),
                merged, None)
        res = out_l if fr.kind == "chooseleaf" else out_h
        res = _compact_rows(res.T)
        if numrep < result_max:
            res = jnp.concatenate(
                [res, jnp.full((n, result_max - numrep), NONE,
                               dtype=jnp.int32)], axis=1)
        return res[:, :result_max]

    def run(self, xs, reweight, result_max: int,
            block: int = DEFAULT_BLOCK):
        """Full do_rule: returns (N, result_max) NONE-compacted placements."""
        fr = self.fr
        numrep = fr.numrep_arg
        if numrep <= 0:
            numrep += result_max
        n = xs.shape[0]
        if numrep <= 0:
            return jnp.full((n, result_max), NONE, dtype=jnp.int32)
        Rf = fr.tries + numrep
        R0 = min(numrep + block, Rf)

        if self._pallas is not None:
            return self._run_pallas(xs, reweight, result_max, numrep, R0, Rf)

        hw, lw, lb = self._winners(xs, reweight, R0)
        out_h, out_l, ovf = _consume(hw, lw, lb, numrep, fr.tries, R0, n)

        def slow(_):
            hw2, lw2, lb2 = self._winners(xs, reweight, Rf)
            oh, ol, _ = _consume(hw2, lw2, lb2, numrep, fr.tries, Rf, n)
            return oh, ol

        out_h, out_l = jax.lax.cond(
            jnp.any(ovf), slow, lambda _: (out_h, out_l), None)
        res = out_l if fr.kind == "chooseleaf" else out_h
        res = _compact_rows(res)
        if numrep < result_max:
            res = jnp.concatenate(
                [res, jnp.full((n, result_max - numrep), NONE,
                               dtype=jnp.int32)], axis=1)
        return res[:, :result_max]
