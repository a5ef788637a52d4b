"""CDLP — community detection by synchronous label propagation.

Re-design of `examples/analytical_apps/cdlp/cdlp.h` +
`cdlp_utils.h::update_label_fast`: labels start as vertex ids; each of
`max_round` rounds every vertex adopts the most frequent label among its
out-neighbors (previous-round values), ties broken toward the smallest
label (the reference sorts labels ascending and keeps the first strict
maximum).

TPU formulation of the mode computation — a sort and two scans over
the pairs it put in order (no per-vertex hash map, no scatter):

  1. gather labels, read one per edge (in the dynamic branch: each
     label's rank in the pass's live universe, `_live_labels`),
  2. sort edge (src, label) pairs (`_sorted_pairs`, three branches),
  3. give each entry its position in its run of equal (src, label)
     pairs (`ops/segment.run_position`): at a run's last entry that is
     the run's length, and less before it,
  4. scan the pair (position, label) down each row under the order
     "larger position, then smaller label"
     (`ops/segment.segment_top_label`),
  5. read each row's answer at the row's last entry: the smallest
     label among its longest runs (a rank, decoded a row at a time).

Everything is O(E log E) on device with static shapes; multi-edges
contribute multiplicity exactly like the reference's neighbor scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats
from libgrape_lite_tpu.ops.segment import (
    pull_gather,
    run_position,
    segment_top_label,
)
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

# which sort the last extracted query's passes took (docs/OBSERVABILITY.md):
# `branch` is what the shapes admit (`static` / `dynamic` / `wide`),
# `universe` the distinct labels each pass saw before it sorted (-1 from
# a pass that computes no predicate), `packed_passes` how many of them
# fitted `u_budget` and so took the dynamic branch's packed arm
CDLP_STATS = _FedStats("cdlp", {
    "branch": "", "u_budget": 0, "passes": 0, "packed_passes": 0,
    "universe": [],
})


def _note_pass(universe, done, n_live):
    """`universe` with `n_live` in the slot of the pass that follows
    `done` finished ones (a slice update: the round holds no scatter
    but the sort's own)."""
    return jax.lax.dynamic_update_slice(
        universe, jnp.reshape(n_live, (1,)), (done,))


class CDLP(ParallelAppBase):
    load_strategy = LoadStrategy.kOnlyOut
    message_strategy = MessageStrategy.kAlongOutgoingEdgeToOuterVertex
    result_format = "int"
    replicated_keys = frozenset({"step", "lut", "universe"})

    def __init__(self, max_round: int = 10, label_dtype=np.int64):
        self.max_round = max_round
        self.label_dtype = label_dtype
        # test hooks: force the wide (variadic-sort) path even when the
        # packed-uint32 key would fit / force the dynamic-compression
        # path even when the static LUT pack would fit / shrink the
        # dynamic universe budget to exercise the in-jit wide fallback
        self._force_wide = False
        self._force_dynamic = False
        self._u_budget_override: int | None = None

    def init_state(self, frag, max_round: int | None = None):
        if max_round is not None:
            self.max_round = max_round
        import jax

        eff_dt = np.dtype(self.label_dtype)
        if eff_dt == np.int64 and not jax.config.jax_enable_x64:
            # device arrays will be int32 anyway; build host arrays in
            # the effective dtype so the BIG sentinel doesn't wrap
            eff_dt = np.dtype(np.int32)
        raw = np.asarray(frag.dev.oids)
        if raw.max(initial=0) >= np.iinfo(eff_dt).max:
            raise ValueError(
                f"vertex ids exceed the {eff_dt} label range; enable "
                "jax_enable_x64 (or pass label_dtype=np.int64 under x64) "
                "for 64-bit ids"
            )
        oids = raw.astype(eff_dt)
        big = np.iinfo(eff_dt).max
        labels = np.where(oids >= 0, oids, big)
        # static sorted label universe (labels only ever move between
        # existing ids); +1 slot so searchsorted results stay in range
        lut = np.sort(np.append(labels.reshape(-1), big))
        # one slot a pass: the distinct labels it saw (CDLP_STATS)
        universe = np.full((max(self.max_round, 1),), -1, np.int32)
        state = {"labels": labels, "step": np.int32(0), "lut": lut,
                 "universe": universe}
        return state

    def _sort_plan(self, n_pad: int, vp: int):
        """(branch, bits of a packed key's label field, live-universe
        budget) the shapes admit: `static` packs (src, rank in the
        initial id universe) into 32 bits, `dynamic` decides each pass
        by the distinct labels left whether (src, rank in the live
        universe) fits, `wide` sorts two keys."""
        rank_bits = max(1, int(np.ceil(np.log2(n_pad + 2))))
        src_bits = max(1, int(np.ceil(np.log2(vp + 2))))
        if rank_bits + src_bits <= 32 and not (
            self._force_wide or self._force_dynamic
        ):
            return "static", rank_bits, 0
        if 32 - src_bits >= 10 and not self._force_wide:
            dyn_bits = 32 - src_bits
            u_budget = min(1 << dyn_bits,
                           int(2 ** np.ceil(np.log2(n_pad + 2))))
            if self._u_budget_override is not None:
                u_budget = min(self._u_budget_override, 1 << dyn_bits)
            return "dynamic", dyn_bits, u_budget
        return "wide", 0, 0

    def _live_labels(self, full, vp):
        """What a pass reads of the gathered state `full` before its
        entries: `(n_live, values, fill, table)`.

        `n_live` is the distinct labels in `full` (the pad label among
        them where a vertex holds it): the predicate of the dynamic
        branch's `lax.cond` and the pass's slot of `universe`; -1 where
        the shapes compute none.  `values` is the V-wide table the
        pull's gather reads one entry an edge from, `fill` what a
        masked entry reads instead, `table` what turns the fold's
        answer back into a label (None: it is one already).

        Outside the dynamic branch `values` is `full` itself.  In it
        the live universe is ONE V-wide sort of `full` with the vertex
        ids beside it, and nothing searches: a sorted value opens a run
        where it differs from the one before it, the runs counted are
        `n_live`, the runs before a value are its rank in the live
        universe, a second V-wide sort by vertex id carries the ranks
        back to vertex order (`values`), and the run openers sorted
        again are the distinct labels in rank order (`table`, `big`
        behind the last).  The table ascends, so rank order is label
        order: entries sort, count and fold by rank exactly as they
        would by label (`_mode_fold`), and ranks are dense, so the
        packed key holds them whenever `n_live` fits the budget.

        Every pass of the dynamic branch builds all of it, whichever
        arm its `cond` then takes: on the v5e the three sorts, the
        flags and the `cumsum` cost 0.2 ms a pass at 131,072 ids and
        1.0 ms at 524,288, where the search this replaced
        (`searchsorted(lut, full)`, twenty dependent V-wide gather
        steps, and a mark scatter) cost 17.4 and 77.7 (PERF.md section
        6, PR 37; the ranks' way back by a scatter costs three times
        the sort's).

        Named for the device trace: `grape.cdlp.universe` on the sort
        and the count, `grape.cdlp.live` on the ranks' way back and
        the table."""
        n_pad = full.shape[0]
        dt = full.dtype
        big = jnp.asarray(np.iinfo(np.dtype(dt).name).max, dt)
        if self._sort_plan(n_pad, vp)[0] != "dynamic":
            return jnp.int32(-1), full, big, None
        ids = jnp.arange(n_pad, dtype=jnp.int32)
        # none of the three sorts needs to be stable: equal labels share
        # a rank whichever comes first, the vertex ids are distinct,
        # and the table has one operand (see `_sorted_pairs`)
        with jax.named_scope("grape.cdlp.universe"):
            su, perm = jax.lax.sort((full, ids), num_keys=1, is_stable=False)
            first = jnp.concatenate(
                [jnp.ones((1,), bool), su[1:] != su[:-1]])
            n_live = first.sum(dtype=jnp.int32)
        with jax.named_scope("grape.cdlp.live"):
            uidx = jnp.cumsum(first.astype(jnp.int32)) - 1
            _, rank = jax.lax.sort((perm, uidx), num_keys=1, is_stable=False)
            table = jax.lax.sort(jnp.where(first, su, big), is_stable=False)
        return n_live, rank, jnp.int32(0), table

    def _mode_fold(self, src, val, lut, vp, n_live, table, row_ptr=None):
        """Per-row mode label from one (src, value) edge multiset:
        sort, then count and choose by scans over the sorted pairs:
        the TPU counting kernel.

        `val` is what the pull's gather read for each entry from
        `_live_labels`' `values`, and `n_live` and `table` are that
        call's: labels and no table outside the dynamic branch, in it
        ranks in the live universe, which order, tie and repeat as
        their labels do.

        After `_sorted_pairs`, `(ss, vv)` is in lexicographic order in
        every branch: equal pairs are contiguous, rows are contiguous,
        padding (`ss == vp`) is last.  An entry's position in its run
        is the run's length at the run's last entry and less before
        it, so the row's largest position is its largest run length,
        and the answer is the smallest value that reaches it: a
        multi-edge counts as often as it is held, ties go to the
        smallest label, a row with no entry returns `big`.  Ranks are
        decoded after the fold, a row at a time: `table[answer]` for
        `vp` rows (0.94 ms a pass at 131,072 rows on the v5e), where
        the search-and-pack this replaced ranked every entry by a
        binary search and decoded every entry after the sort (2,270 ms
        a pass at 18.95M entries; PERF.md section 6, PR 37).

        `row_ptr` is the offsets of the sorted rows where the caller
        knows them: for a whole padded CSR its `indptr`, because the
        CSR's contract (graph/csr.py: real edges sorted by row, every
        masked entry behind the last row) makes the sorted `ss` equal
        `edge_src`.  Without it the offsets are looked up in `ss`
        (`ops/segment.segment_top_label`); the round always has it.

        Named for the device trace (metadata only, like the pull's):
        `grape.cdlp.sort` on key building, the sort (whichever branch
        and arm) and the shifts back to `(ss, vv)`;
        `grape.cdlp.count` on the positions in the runs;
        `grape.pull.fold` on the scan into rows; `grape.cdlp.rank` on
        what ranks still cost beside the gather and the sort, the
        V-wide decode."""
        with jax.named_scope("grape.cdlp.sort"):
            ss, vv = self._sorted_pairs(src, val, lut, vp, n_live)
        with jax.named_scope("grape.cdlp.count"):
            pos = run_position(ss, vv)
        top = segment_top_label(pos, vv, ss, vp, row_ptr=row_ptr)
        if table is None:
            return top
        with jax.named_scope("grape.cdlp.rank"):
            # `segment_top_label`'s answer for a row with no entry is
            # the rank dtype's largest value, and no rank
            none = top == jnp.iinfo(top.dtype).max
            lab = table.at[jnp.where(none, 0, top)].get(
                mode="promise_in_bounds")
            return jnp.where(none, jnp.iinfo(lab.dtype).max, lab)

    def _sorted_pairs(self, src, val, lut, vp, n_live):
        """The (src, value) pairs in lexicographic order, by whichever
        of the three sorts the shapes admit (`_sort_plan`)."""
        n_pad = lut.shape[0] - 1
        branch, bits, u_budget = self._sort_plan(n_pad, vp)
        from jax import lax as jlax

        def _wide(pair):
            # ONE variadic lexicographic sort over the (src, value)
            # pair — `lax.sort` with num_keys=2 compares tuples
            # directly, so no rank LUT, no permutation gather, and no
            # second stable sort (the old lexsort fallback paid both).
            # Works at any label width the dtype admits.  Every operand
            # is a key, so equal entries are the same entry and the
            # sort need not be stable: a stable one carries an E-wide
            # iota beside its operands: 46-91% more time, 76 MB and
            # 2 MB of code at 19M entries (PERF.md section 6, PR 37).
            return jlax.sort(pair, num_keys=2, is_stable=False)

        if branch == "static":
            rank_bits = bits
            # labels always belong to the initial id universe, so they
            # rank into a static sorted LUT; packing (src, rank) into
            # one uint32 key lets ONE sort replace the two-key lexsort,
            # and (ss, ll) decode straight from the sorted keys — no
            # permutation gather
            rank = jnp.searchsorted(lut, val).astype(jnp.uint32)
            key = (src.astype(jnp.uint32) << rank_bits) | rank
            key = jnp.sort(key)
            ss = (key >> rank_bits).astype(jnp.int32)
            ll = lut[
                jnp.minimum(key & jnp.uint32((1 << rank_bits) - 1),
                            jnp.uint32(n_pad)).astype(jnp.int32)
            ]
            return ss, ll
        if branch == "dynamic":
            # Dynamic label-universe compression (VERDICT r4 next #2;
            # reference XL-graph counterpart: cdlp_opt.h): when the
            # STATIC universe (n_pad ids) outgrows the 32-bit pack, the
            # LIVE universe usually hasn't — label propagation
            # coalesces labels geometrically, so after the first couple
            # of rounds the distinct-label count is far below n_pad.
            # The entries come as ranks in the live universe
            # (`_live_labels`), and an in-jit lax.cond packs (src,
            # rank) into one uint32 key when the universe fits
            # 2^(32 - src_bits), else sorts the pair: the arms differ
            # by their sort and by nothing else.  Early all-distinct
            # rounds take the wide arm; coalesced rounds (the bulk of
            # max_round on a graph with communities) the packed one.
            dyn_bits = bits

            def _packed(pair):
                src, rank = pair
                key = (src.astype(jnp.uint32) << dyn_bits) | rank.astype(
                    jnp.uint32)
                key = jlax.sort(key, is_stable=False)  # as in `_wide`
                return ((key >> dyn_bits).astype(jnp.int32),
                        (key & jnp.uint32((1 << dyn_bits) - 1)).astype(
                            jnp.int32))

            return jlax.cond(
                n_live <= jnp.int32(u_budget), _packed, _wide, (src, val))
        # wide path (vertices/shard beyond even the dynamic pack, or
        # forced): see _wide
        return _wide((src, val))

    def _propagate(self, ctx, frag, labels, lut):
        """One pass: (the new labels, the distinct labels it saw)."""
        oe = frag.oe
        vp = frag.vp
        dt = labels.dtype
        big = jnp.asarray(np.iinfo(np.dtype(dt).name).max, dt)

        full = ctx.gather_state(labels)
        n_live, values, fill, table = self._live_labels(full, vp)
        val = pull_gather(values, oe.edge_nbr, mask=oe.edge_mask, fill=fill)
        with jax.named_scope("grape.cdlp.sort"):
            src = jnp.where(oe.edge_mask, oe.edge_src, jnp.int32(vp))
        new_lab = self._mode_fold(src, val, lut, vp, n_live, table,
                                  row_ptr=oe.indptr)

        with jax.named_scope("grape.app.update"):
            has_out = frag.out_degree > 0
            keep = jnp.logical_or(~frag.inner_mask, ~has_out)
            return jnp.where(
                jnp.logical_or(keep, new_lab == big), labels, new_lab
            ), n_live

    def peval(self, ctx: StepContext, frag, state):
        # reference PEval: step=1, one propagation (cdlp.h PEval)
        labels, n_live = self._propagate(
            ctx, frag, state["labels"], state["lut"])
        state = dict(state, labels=labels, step=jnp.int32(1),
                     universe=_note_pass(state["universe"], 0, n_live))
        active = jnp.int32(1 if self.max_round > 1 else 0)
        return state, active

    def inceval(self, ctx: StepContext, frag, state):
        step = state["step"] + 1
        labels, n_live = self._propagate(
            ctx, frag, state["labels"], state["lut"])
        active = jnp.where(step >= jnp.int32(self.max_round), jnp.int32(0), jnp.int32(1))
        universe = _note_pass(state["universe"], state["step"], n_live)
        return dict(state, labels=labels, step=step,
                    universe=universe), active

    def invariants(self, frag, state):
        # Labels are NOT monotone under mode adoption (the most
        # frequent neighbor label can exceed the current one — that is
        # why CDLP runs a fixed round budget), so the sound invariant
        # is universe membership: every label is an id that existed at
        # init (<= the max initial label) or the pad sentinel.
        from libgrape_lite_tpu.guard.invariants import Invariant

        def in_universe(dev, prev, cur):
            lab = cur["labels"]
            dt = lab.dtype
            big = jnp.asarray(np.iinfo(np.dtype(dt).name).max, dt)
            # the largest real id in the sorted universe (the lut also
            # holds one sentinel per padded row, so filter rather than
            # index from the end)
            lut = cur["lut"]
            max_id = jnp.max(jnp.where(lut < big, lut, jnp.asarray(-1, dt)))
            ok = jnp.logical_and(
                lab >= 0,
                jnp.logical_or(lab <= max_id, lab == big),
            )
            nbad = (~ok).sum().astype(jnp.int32)
            return nbad == 0, nbad.astype(jnp.float32)

        return [Invariant(
            "cdlp_label_universe", in_universe, ("labels", "lut"),
            "labels stay within the initial id universe (or the pad "
            "sentinel)",
        )]

    def _record_stats(self, labels, state) -> None:
        """CDLP_STATS from the extracted state: host side, after the
        query and outside its wall (the leaf comes with the labels)."""
        branch, _, u_budget = self._sort_plan(labels.size, labels.shape[-1])
        passes = int(np.asarray(state["step"]))
        universe = np.asarray(state["universe"]).reshape(-1)[:passes]
        CDLP_STATS.update(
            branch=branch, u_budget=u_budget, passes=passes,
            packed_passes=int(((universe >= 0) & (universe <= u_budget)).sum()),
            universe=universe.tolist(),
        )

    def finalize(self, frag, state):
        labels = np.asarray(state["labels"])
        self._record_stats(labels, state)
        if frag.is_string_keyed():
            # device labels are pid surrogates (edgecut oids array);
            # map back to the original string ids for output
            flat = labels.reshape(-1)
            uniq = np.unique(flat[flat >= 0])
            lut = {
                int(p): o
                for p, o in zip(uniq, np.asarray(frag.pid_to_oid(uniq)).tolist())
            }
            return np.vectorize(
                lambda x: lut.get(int(x), -1), otypes=[object]
            )(labels)
        return labels


class CDLPOpt(CDLP):
    """CDLP with the reference's first-round shortcut
    (`cdlp_opt.h:139-162`, `cdlp_opt_ud.h:148-162`): initial labels are
    all-distinct vertex ids, so "most frequent, ties to smallest"
    degenerates to a plain neighbor minimum — one O(E) segment_min pull
    replaces the O(E log E) sort-mode pipeline for round 1.  (Like the
    reference shortcut, this assumes a simple graph: a parallel edge
    would give its endpoint's label multiplicity ≥ 2 in round 1 and the
    true mode could differ from the min.  LDBC inputs are simple.)

    The reference's remaining opt machinery maps as follows (argued in
    PARITY.md):
      * sparse change-frontier rounds (`cdlp_opt_ud.h:89-120`,
        threshold in `cdlp_opt_context.h`) — N/A on TPU: the dense
        masked formulation recomputes every row at full VPU width
        regardless, so a sparse frontier saves nothing and costs a
        gather;
      * `update_label_fast_{jump,sparse,dense}` per-vertex counting
        kernels (`cdlp_utils.h`) — scalar-CPU/SIMD concerns; the
        packed-key sort + run-length encode here IS the vectorized
        counting kernel;
      * `ud` (undirected-only load) — the oe==ie aliased CSR already
        halves storage for undirected graphs (fragment/edgecut.py).
    Output is bit-identical to CDLP for every round count.
    """

    def peval(self, ctx: StepContext, frag, state):
        labels = state["labels"]
        oe = frag.oe
        dt = labels.dtype
        big = jnp.asarray(np.iinfo(np.dtype(dt).name).max, dt)
        full = ctx.gather_state(labels)
        cand = pull_gather(full, oe.edge_nbr, mask=oe.edge_mask, fill=big)
        mn = self.segment_reduce(cand, oe.edge_src, frag.vp, "min",
                                 row_ptr=oe.indptr)
        has_out = frag.out_degree > 0
        keep = jnp.logical_or(~frag.inner_mask, ~has_out)
        new = jnp.where(jnp.logical_or(keep, mn == big), labels, mn)
        state = dict(state, labels=new, step=jnp.int32(1))
        return state, jnp.int32(1 if self.max_round > 1 else 0)
