"""Vectorized CRUSH mapper — crush_do_rule over a batch of PGs at once.

The TPU rebuild of the reference's hot placement loop (ref:
src/crush/mapper.c crush_do_rule / crush_choose_{firstn,indep} /
bucket_straw2_choose — SURVEY.md §3.4): placement is pure integer math,
so the whole rule program is executed as fixed-shape array ops over a
(B,) batch of inputs. Data-dependent retry loops become a static unroll
(tunables.choose_total_tries) with lane masks; the bucket hierarchy
descent becomes max_depth gather steps; every draw stays uint32/float32
so results are bit-identical to the scalar oracle (oracle.py) — pinned
by parity tests.

Call shape: VectorMapper(map).do_rule(rule_id, xs, weights, result_max)
-> (B, R) int32 device ids with CRUSH_ITEM_NONE holes (indep) or
NONE-padded tails (firstn).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .hash import hash32_2, hash32_3, hash32_4
from .map import (ALG_LIST, ALG_STRAW, ALG_STRAW2, ALG_TREE, ALG_UNIFORM,
                  CRUSH_ITEM_NONE, CrushMap, STEP_CHOOSE_FIRSTN,
                  STEP_CHOOSE_INDEP, STEP_CHOOSELEAF_FIRSTN,
                  STEP_CHOOSELEAF_INDEP, STEP_EMIT, STEP_TAKE)
from .oracle import ln16_table

_NONE = np.int32(CRUSH_ITEM_NONE)


def _mulhi32(h, w):
    """Exact (h * w) >> 32 for uint32 operands without 64-bit ints:
    16-bit split with carry tracking (the tree draw needs the high
    word of a 32x32 product, like mapper.c's __u64 shift)."""
    a, b = h >> 16, h & jnp.uint32(0xFFFF)
    c, d = w >> 16, w & jnp.uint32(0xFFFF)
    mid = a * d
    s = mid + b * c
    carry = (s < mid).astype(jnp.uint32)
    lo = b * d
    s2 = s + (lo >> 16)
    carry2 = (s2 < s).astype(jnp.uint32)
    return a * c + (s2 >> 16) + ((carry + carry2) << 16)


class VectorMapper:
    def __init__(self, m: CrushMap, draw: str = "fixed"):
        if draw not in ("fixed", "float"):
            raise ValueError(f"draw must be 'fixed' or 'float', got {draw!r}")
        self.m = m
        self.draw = draw
        p = m.pack()
        self.tries = m.tunables.choose_total_tries
        self.max_depth = p.max_depth
        self.S = p.max_size
        # device-resident map tables
        self.t_items = jnp.asarray(p.items)                    # (NB, S) i32
        self.t_w32 = jnp.asarray(
            (p.weights.astype(np.float64) / 65536.0).astype(np.float32))
        self.t_wzero = jnp.asarray(p.weights == 0)             # (NB, S)
        self.t_size = jnp.asarray(p.size)                      # (NB,)
        self.t_alg = jnp.asarray(p.alg)
        self.t_type = jnp.asarray(p.type_id)
        # list-bucket cumulative weights, split for 32-bit exact math
        sw = p.sum_weights.astype(np.uint64)
        self.t_sw_lo = jnp.asarray((sw & 0xFFFF).astype(np.uint32))
        self.t_sw_hi = jnp.asarray((sw >> 16).astype(np.uint32))
        self.t_iw_u32 = jnp.asarray(p.weights.astype(np.uint32))
        self.t_ln16 = jnp.asarray(ln16_table())
        if draw == "fixed":
            # per-distinct-weight q = A48 // w tables (ln48.py): the
            # whole s64 draw/divide/compare pipeline reduces to two u32
            # gathers + a lexicographic argmin, exact vs the oracle
            from .ln48 import quotient_tables
            widx_of, qhi, qlo = quotient_tables(p.weights.ravel())
            widx = np.zeros(p.weights.shape, dtype=np.int32)
            for w, i in widx_of.items():
                widx[p.weights == w] = i
            self.t_widx = jnp.asarray(widx)              # (NB, S)
            self.t_qhi = jnp.asarray(qhi.reshape(-1))    # (D * 65536,)
            self.t_qlo = jnp.asarray(qlo.reshape(-1))
        self.algs_used = set(int(a) for a in np.unique(p.alg) if a != 0)
        self.S_uniform = p.max_size_by_alg.get(ALG_UNIFORM, 1)
        if p.tree_nodes is not None:
            # calc_tree_nodes already wraps mod 2^32 (__u32 parity
            # with the oracle); the cast is lossless
            self.t_tree_nodes = jnp.asarray(
                p.tree_nodes.astype(np.uint32))
            self.t_tree_nn = jnp.asarray(p.tree_num_nodes)
            self.tree_depth = int(np.log2(p.tree_nodes.shape[1])) + 1
        if p.straws is not None:
            st = p.straws.astype(np.uint64)
            self.t_straw_hi = jnp.asarray((st >> 16).astype(np.uint32))
            self.t_straw_lo = jnp.asarray((st & 0xFFFF).astype(np.uint32))
            self.t_straw_zero = jnp.asarray(p.straws == 0)
        self._jitted = {}

    # -- bucket choose (batched over lanes) ---------------------------------

    def _rows(self, node):
        """bucket id (negative) -> packed row; invalid lanes -> row 0."""
        row = -1 - node
        return jnp.clip(row, 0, self.t_items.shape[0] - 1)

    def _straw2(self, row, x, r):
        items = self.t_items[row]                       # (B, S)
        slot_ok = (jnp.arange(self.S)[None, :] < self.t_size[row][:, None]) \
            & ~self.t_wzero[row]
        r_b = jnp.asarray(r, jnp.uint32)
        r_b = r_b[:, None] if r_b.ndim else r_b
        h = hash32_3(x[:, None], items.astype(jnp.uint32), r_b, np_like=jnp)
        h16 = (h & jnp.uint32(0xFFFF)).astype(jnp.int32)
        if self.draw == "fixed":
            best = self._straw2_best_fixed(row, h16, slot_ok)
        else:
            w32 = self.t_w32[row]
            draws = self.t_ln16[h16] / w32
            draws = jnp.where(slot_ok, draws, -jnp.inf)
            best = jnp.argmax(draws, axis=1)
        item = jnp.take_along_axis(items, best[:, None], axis=1)[:, 0]
        any_ok = slot_ok.any(axis=1)
        return jnp.where(any_ok, item, _NONE)

    def _straw2_best_fixed(self, row, h16, slot_ok):
        """Winning slot under reference integer draw semantics: first
        strictly-smallest q = A48 // w (48-bit, as u32 hi/lo pair) —
        lexicographic argmin with first-wins ties (mapper.c keeps the
        earlier item unless a later draw is STRICTLY greater)."""
        umax = jnp.uint32(0xFFFFFFFF)
        flat = self.t_widx[row] * 65536 + h16           # (B, S)
        qhi = jnp.where(slot_ok, self.t_qhi[flat], umax)
        qlo = jnp.where(slot_ok, self.t_qlo[flat], umax)
        m1 = qhi.min(axis=1, keepdims=True)
        cand = qhi == m1
        lo_m = jnp.where(cand, qlo, umax)
        m2 = lo_m.min(axis=1, keepdims=True)
        return jnp.argmax(cand & (lo_m == m2), axis=1)  # first winner

    def _uniform(self, row, x, r):
        size = self.t_size[row]                         # (B,)
        bid = (-1 - row).astype(jnp.uint32)
        B = row.shape[0]
        # unroll bound: largest UNIFORM bucket, not the global max size
        # (a big straw2 root must not bloat every uniform choose)
        SU = self.S_uniform
        perm = jnp.broadcast_to(jnp.arange(SU, dtype=jnp.int32), (B, SU))
        cols = jnp.arange(SU, dtype=jnp.int32)[None, :]
        for i in range(SU - 1):
            rem = jnp.maximum(size - i, 1)
            h = hash32_3(x, bid, jnp.uint32(i), np_like=jnp)
            j = i + (h % rem.astype(jnp.uint32)).astype(jnp.int32)
            vi = perm[:, i]
            vj = jnp.take_along_axis(perm, j[:, None], axis=1)[:, 0]
            active = (i < size)[:, None]
            swapped = jnp.where(cols == i, vj[:, None],
                                jnp.where(cols == j[:, None], vi[:, None],
                                          perm))
            perm = jnp.where(active, swapped, perm)
        r_arr = jnp.broadcast_to(jnp.asarray(r, jnp.int32), (B,)) \
            if jnp.ndim(r) == 0 else r.astype(jnp.int32)
        pr = r_arr % jnp.maximum(size, 1)
        slot = jnp.take_along_axis(perm, pr[:, None], axis=1)[:, 0]
        item = jnp.take_along_axis(self.t_items[row], slot[:, None],
                                   axis=1)[:, 0]
        return jnp.where(size > 0, item, _NONE)

    def _list(self, row, x, r):
        items = self.t_items[row]
        bid = (-1 - row).astype(jnp.uint32)
        r_b = jnp.asarray(r, jnp.uint32)
        r_b = r_b[:, None] if r_b.ndim else r_b
        h = hash32_4(x[:, None], items.astype(jnp.uint32), r_b,
                     bid[:, None], np_like=jnp)
        h16 = h & jnp.uint32(0xFFFF)
        # exact floor((h16 * sum_w) / 2^16) < item_w in 32-bit pieces
        p_lo = h16 * self.t_sw_lo[row]
        p_hi = h16 * self.t_sw_hi[row]
        lhs = p_hi + (p_lo >> 16)
        cond = lhs < self.t_iw_u32[row]
        slot_ok = jnp.arange(self.S)[None, :] < self.t_size[row][:, None]
        mask = cond & slot_ok
        rev = mask[:, ::-1]
        pos = jnp.argmax(rev, axis=1)
        idx = self.S - 1 - pos
        found = rev.any(axis=1)
        slot = jnp.where(found, idx, 0)
        item = jnp.take_along_axis(items, slot[:, None], axis=1)[:, 0]
        return jnp.where(self.t_size[row] > 0, item, _NONE)

    def _tree(self, row, x, r):
        """In-order binary-tree walk, all lanes in lockstep for
        tree_depth steps (ref: mapper.c bucket_tree_choose). Terminal
        (odd) nodes self-loop: half = lowest-set-bit(n) >> 1 is 0."""
        nodes_b = self.t_tree_nodes[row]              # (B, MN)
        nn = self.t_tree_nn[row]                      # (B,)
        n = (nn >> 1).astype(jnp.int32)
        bid = (-1 - row).astype(jnp.uint32)
        r_b = jnp.broadcast_to(jnp.asarray(r, jnp.uint32), n.shape) \
            if jnp.ndim(r) == 0 else r.astype(jnp.uint32)
        root_w = jnp.take_along_axis(nodes_b, n[:, None], axis=1)[:, 0]

        def walk(_i, n):
            half = (n & -n) >> 1                      # 0 when n is odd
            w = jnp.take_along_axis(nodes_b, n[:, None], axis=1)[:, 0]
            h = hash32_4(x, n.astype(jnp.uint32), r_b, bid, np_like=jnp)
            t = _mulhi32(h, w)
            left = n - half
            wl = jnp.take_along_axis(nodes_b, left[:, None],
                                     axis=1)[:, 0]
            return jnp.where(half > 0,
                             jnp.where(t < wl, left, n + half), n)
        # fori_loop keeps the traced program small: the walk body is
        # emitted once, not tree_depth times per descend level
        n = jax.lax.fori_loop(0, self.tree_depth, walk, n)
        item = jnp.take_along_axis(self.t_items[row], (n >> 1)[:, None],
                                   axis=1)[:, 0]
        ok = ((n & 1) == 1) & (root_w > 0)
        return jnp.where(ok, item, _NONE)

    def _straw(self, row, x, r):
        """Legacy straw: draw = h16 * straw (48-bit) with the replica
        rank hashed in, first-wins max, compared as (hi, lo16) u32
        pairs (ref: bucket_straw_choose hashes (x, item, r))."""
        items = self.t_items[row]
        r_b = jnp.asarray(r, jnp.uint32)
        r_b = r_b[:, None] if r_b.ndim else r_b
        h = hash32_3(x[:, None], items.astype(jnp.uint32), r_b,
                     np_like=jnp)
        h16 = h & jnp.uint32(0xFFFF)
        slot_ok = jnp.arange(self.S)[None, :] < self.t_size[row][:, None]
        hi = h16 * self.t_straw_hi[row] \
            + ((h16 * self.t_straw_lo[row]) >> 16)
        lo = (h16 * self.t_straw_lo[row]) & jnp.uint32(0xFFFF)
        hi = jnp.where(slot_ok, hi, 0)
        lo = jnp.where(slot_ok, lo, 0)
        m1 = hi.max(axis=1, keepdims=True)
        cand = hi == m1
        lo_m = jnp.where(cand, lo, 0)
        m2 = lo_m.max(axis=1, keepdims=True)
        best = jnp.argmax(cand & (lo_m == m2), axis=1)  # first winner
        item = jnp.take_along_axis(items, best[:, None], axis=1)[:, 0]
        dead = jnp.take_along_axis(self.t_straw_zero[row], best[:, None],
                                   axis=1)[:, 0]
        return jnp.where((self.t_size[row] > 0) & ~dead, item, _NONE)

    def _bucket_choose(self, node, x, r):
        """node (B,) bucket ids (negative) -> chosen child item (B,)."""
        row = self._rows(node)
        alg = self.t_alg[row]
        out = jnp.full(node.shape, _NONE, dtype=jnp.int32)
        if ALG_STRAW2 in self.algs_used:
            out = jnp.where(alg == ALG_STRAW2, self._straw2(row, x, r), out)
        if ALG_UNIFORM in self.algs_used:
            out = jnp.where(alg == ALG_UNIFORM, self._uniform(row, x, r), out)
        if ALG_LIST in self.algs_used:
            out = jnp.where(alg == ALG_LIST, self._list(row, x, r), out)
        if ALG_TREE in self.algs_used:
            out = jnp.where(alg == ALG_TREE, self._tree(row, x, r), out)
        if ALG_STRAW in self.algs_used:
            out = jnp.where(alg == ALG_STRAW, self._straw(row, x, r), out)
        return out

    # -- descent / rejection ------------------------------------------------

    def _item_type(self, item):
        row = self._rows(item)
        return jnp.where(item >= 0, 0, self.t_type[row])

    def _descend(self, node, x, r, want_type: int):
        cur = node
        for _ in range(self.max_depth + 1):
            t = self._item_type(cur)
            done = (t == want_type) | (cur == _NONE)
            dead_end = (cur >= 0) & (t != want_type)
            active = ~done & ~dead_end
            nxt = self._bucket_choose(jnp.where(active, cur, -1), x, r)
            cur = jnp.where(active, nxt, jnp.where(dead_end, _NONE, cur))
        final_ok = self._item_type(cur) == want_type
        return jnp.where(final_ok & (cur != _NONE), cur, _NONE)

    def _is_out(self, weights, item, x):
        """weights: (n_devices,) int32 16.16; item may be NONE/bucket."""
        dev = jnp.clip(item, 0, weights.shape[0] - 1)
        w = weights[dev]
        h16 = hash32_2(x, item.astype(jnp.uint32), np_like=jnp) \
            & jnp.uint32(0xFFFF)
        rejected = jnp.where(w >= 0x10000, False,
                             jnp.where(w == 0, True,
                                       h16.astype(jnp.int32) >= w))
        return jnp.where(item >= 0, rejected, False)

    # -- choose -------------------------------------------------------------

    def _choose_indep(self, take, x, numrep: int, want_type: int,
                      weights, to_leaf: bool):
        B = x.shape[0]
        out0 = jnp.full((B, numrep), _NONE, dtype=jnp.int32)
        leaves0 = jnp.full((B, numrep), _NONE, dtype=jnp.int32)

        # one retry round is traced once; lax.fori_loop runs `tries` of
        # them (the reference's data-dependent retry loop, made static)
        def round_body(rnd, carry):
            out, leaves = carry
            for rep in range(numrep):
                r = (jnp.uint32(rep) + rnd.astype(jnp.uint32)
                     * jnp.uint32(numrep))
                undecided = out[:, rep] == _NONE
                item = self._descend(take, x, r, want_type)
                valid = item != _NONE
                collide = (item[:, None] == out).any(axis=1)
                ok = undecided & valid & ~collide
                if to_leaf:
                    leaf = self._descend(jnp.where(valid, item, -1), x, r, 0)
                    lvalid = (leaf != _NONE) \
                        & ~(leaf[:, None] == leaves).any(axis=1) \
                        & ~self._is_out(weights, leaf, x)
                    ok = ok & lvalid
                    leaves = leaves.at[:, rep].set(
                        jnp.where(ok, leaf, leaves[:, rep]))
                else:
                    ok = ok & ~self._is_out(weights, item, x)
                out = out.at[:, rep].set(jnp.where(ok, item, out[:, rep]))
            return out, leaves

        def cond(state):
            rnd, (out, leaves) = state
            undecided = ((leaves if to_leaf else out) == _NONE).any()
            return (rnd < self.tries) & undecided

        def body(state):
            rnd, carry = state
            return rnd + 1, round_body(rnd, carry)

        # while_loop instead of a fixed unroll: nearly every lane
        # succeeds in round 0, so the retry rounds only run (for the
        # whole batch) while some slot is still NONE
        _, (out, leaves) = jax.lax.while_loop(
            cond, body, (jnp.int32(0), (out0, leaves0)))
        return leaves if to_leaf else out

    def _choose_firstn(self, take, x, numrep: int, want_type: int,
                       weights, to_leaf: bool):
        B = x.shape[0]
        out0 = jnp.full((B, numrep), _NONE, dtype=jnp.int32)
        leaves0 = jnp.full((B, numrep), _NONE, dtype=jnp.int32)
        ftotal0 = jnp.zeros((B,), dtype=jnp.int32)

        def make_attempt(rep):
            def attempt(_t, carry):
                out, leaves, ftotal, found = carry
                active = ~found & (ftotal < self.tries)
                r = (jnp.int32(rep) + ftotal).astype(jnp.uint32)
                item = self._descend(take, x, r, want_type)
                valid = item != _NONE
                collide = (item[:, None] == out).any(axis=1)
                ok = active & valid & ~collide
                if to_leaf:
                    leaf = self._descend(jnp.where(valid, item, -1), x, r, 0)
                    lvalid = (leaf != _NONE) \
                        & ~(leaf[:, None] == leaves).any(axis=1) \
                        & ~self._is_out(weights, leaf, x)
                    ok = ok & lvalid
                    leaves = leaves.at[:, rep].set(
                        jnp.where(ok, leaf, leaves[:, rep]))
                else:
                    ok = ok & ~self._is_out(weights, item, x)
                out = out.at[:, rep].set(jnp.where(ok, item, out[:, rep]))
                ftotal = jnp.where(active & ~ok, ftotal + 1, ftotal)
                found = found | ok
                return out, leaves, ftotal, found

            return attempt

        out, leaves, ftotal = out0, leaves0, ftotal0
        for rep in range(numrep):
            found = jnp.zeros((B,), dtype=bool)
            attempt = make_attempt(rep)

            def cond(carry):
                _out, _leaves, ft, fnd = carry
                return (~fnd & (ft < self.tries)).any()

            def body(carry):
                return attempt(0, carry)

            out, leaves, ftotal, found = jax.lax.while_loop(
                cond, body, (out, leaves, ftotal, found))
        return leaves if to_leaf else out

    # -- rule execution -----------------------------------------------------

    def _do_rule_impl(self, rule_id: int, result_max: int, xs, weights):
        rule = self.m.rules[rule_id]
        working = None
        results = []
        B = xs.shape[0]
        for step in rule.steps:
            if step.op == STEP_TAKE:
                working = jnp.full((B, 1), np.int32(step.arg), jnp.int32)
            elif step.op == STEP_EMIT:
                results.append(working)
                working = None
            else:
                numrep = step.arg if step.arg > 0 else result_max + step.arg
                indep = step.op in (STEP_CHOOSE_INDEP, STEP_CHOOSELEAF_INDEP)
                to_leaf = step.op in (STEP_CHOOSELEAF_FIRSTN,
                                      STEP_CHOOSELEAF_INDEP)
                fn = self._choose_indep if indep else self._choose_firstn
                cols = []
                for w in range(working.shape[1]):
                    cols.append(fn(working[:, w], xs, numrep, step.type_id,
                                   weights, to_leaf))
                working = jnp.concatenate(cols, axis=1)
        return jnp.concatenate(results, axis=1)

    def do_rule(self, rule_id: int, xs, weights, result_max: int):
        """xs: (B,) int/uint32 PG seeds; weights: (n_devices,) 16.16
        int32 reweights. Returns (B, R) int32 items, CRUSH_ITEM_NONE
        for unfilled slots."""
        key = (rule_id, result_max)
        fn = self._jitted.get(key)
        if fn is None:
            def impl(tables, xs, weights,
                     _rid=rule_id, _rm=result_max, _self=self):
                # the map tables enter as RUNTIME inputs (a dict
                # pytree), NOT closed-over trace constants: closing
                # over the device arrays let XLA constant-fold the
                # bucket-table gathers at compile time — compile cost
                # scaled with lane count and capped the CPU fallback
                # at 100k-lane sub-batches (r3). A shallow view with
                # tracer-valued t_* attrs routes every method access
                # through the arguments instead.
                import copy as _copy
                view = _copy.copy(_self)
                view.__dict__.update(tables)
                return VectorMapper._do_rule_impl(view, _rid, _rm,
                                                  xs, weights)
            fn = jax.jit(impl)
            self._jitted[key] = fn
        xs = jnp.asarray(xs).astype(jnp.uint32)
        weights = jnp.asarray(weights, jnp.int32)
        return fn(self._table_args(), xs, weights)

    def _table_args(self) -> dict:
        """Every device-resident map table, keyed by attribute name —
        the runtime-input pytree for the jitted rule."""
        return {k: v for k, v in self.__dict__.items()
                if k.startswith("t_")}

    def scan_rule(self, rule_id: int, weights, result_max: int,
                  start: int, sub: int, n_batches: int):
        """Place n_batches consecutive sub-batches of `sub` PGs inside
        ONE device program (lax.scan), seeds generated on device.

        A thousand do_rule dispatches pay a thousand host round
        trips, so throughput benching puts the whole loop on device
        behind one dispatch. Returns (digest, last)
        where digest is an int32 XOR fold over every placement (the
        data dependency that keeps all batches live) and last is the
        final (sub, result_max) placement batch for spot validation.
        """
        key = ("scan", rule_id, result_max, sub, n_batches)
        fn = self._jitted.get(key)
        if fn is None:
            def impl(tables, weights, start, _rid=rule_id,
                     _rm=result_max, _sub=sub, _nb=n_batches,
                     _self=self):
                import copy as _copy
                view = _copy.copy(_self)
                view.__dict__.update(tables)

                def body(carry, i):
                    acc, _last = carry
                    xs = (jnp.arange(_sub, dtype=jnp.uint32)
                          + (start + i * _sub).astype(jnp.uint32))
                    res = VectorMapper._do_rule_impl(
                        view, _rid, _rm, xs, weights)
                    d = jnp.bitwise_xor.reduce(
                        jnp.bitwise_xor.reduce(res, axis=0))
                    return (acc ^ d, res), None
                init = (jnp.int32(0),
                        jnp.zeros((_sub, _rm), jnp.int32))
                (acc, last), _ = jax.lax.scan(
                    body, init, jnp.arange(_nb, dtype=jnp.int32))
                return acc, last
            fn = jax.jit(impl)
            self._jitted[key] = fn
        weights = jnp.asarray(weights, jnp.int32)
        acc, last = fn(self._table_args(), weights, jnp.int32(start))
        return int(jax.device_get(acc)), last


def full_weights(n_devices: int) -> np.ndarray:
    return np.full(n_devices, 0x10000, dtype=np.int32)
