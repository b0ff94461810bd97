"""Tusk's DAG traversals as one jitted boolean-matrix scan on device.

The reference commit rule (consensus/src/lib.rs:224-303) does two kinds of
graph walk per candidate leader:

- ``order_leaders`` calls ``linked()`` once per earlier leader — each call a
  round-by-round BFS over the whole certificate window (lib.rs:247-259);
- ``order_dag`` flattens the causal history of every newly committed leader
  (lib.rs:263-303).

Both are frontier propagations through the round-structured DAG.  Here the
window is a dense tensor — ``exists[w, n]`` (certificate present at slot w,
authority n) and ``parent[w, n, m]`` (cert (w, n) references cert (w-1, m)) —
and a single ``lax.scan`` down the window computes the ENTIRE leader chain:
the frontier is a length-N boolean vector, each step is a vector–matrix
product (int32 matmul → MXU), and when the frontier reaches the leader of an
even round the scan records a committed leader and resets the frontier to
that leader alone (exactly the ``leader = prev_leader`` rebinding in
``order_leaders``).

Slots are fixed-size (static shapes for XLA): slot w holds round
``base_round + w``.  The committee axis N is padded to the committee size;
the window W to a static power-of-two ≥ gc_depth.

Execution model (round 6, the device-resident rewrite): the dense window
LIVES ON DEVICE across calls.  Certificate arrivals stage host-side (an
O(1) list append); the staged batch is flushed in one donated scatter
dispatch per even-round commit opportunity (``window_apply``,
``donate_argnums`` so XLA updates the buffers in place — no host round
trip and no reallocation); commits shift the window with a donated gather
(``window_shift_op``); and the ONLY device→host transfer on the commit
path is the W-bool committed bitmap out of ``leader_commit_scan_counts``.  The
round-5 engine instead kept the window in host numpy, re-uploaded the full
W×N×N parent tensor per ``order_leaders`` call, and paid per-certificate
numpy scatter work on the arrival path.  Neither engine has been measured
on the v5e host (ROADMAP D4 decides the kernel's fate on a benchmark cell).
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import metrics


_donation_warning_handled = False

# Kernel observability: how large the staged flush batches are and how
# many device dispatches the commit path actually issues — the numbers the
# r05→r06 rebuild had to reconstruct from ad-hoc prints.
_m_flush_batch = metrics.histogram(
    "consensus.kernel.flush_batch_size", metrics.COUNT_BUCKETS
)
_m_dispatches = metrics.counter("consensus.kernel.dispatches")
_m_shifts = metrics.counter("consensus.kernel.window_shifts")
_m_fallbacks = metrics.counter("consensus.kernel.python_fallbacks")


def _silence_cpu_donation_warning() -> None:
    """Buffer donation is a no-op (with a warning) on the CPU backend; the
    donated path is still correct there, just copying.  Filter the noise —
    but ONLY on CPU: on a real accelerator that same warning is the one
    diagnostic for a donation regression (a stray live reference forcing
    XLA back to per-flush window copies, the r05 pathology), so it must
    stay visible there.  Called from KernelTusk.__init__, after the
    instance's buffer allocation has already initialized the backend;
    installs at most one process-global filter entry."""
    global _donation_warning_handled
    if _donation_warning_handled:
        return
    _donation_warning_handled = True
    if jax.default_backend() == "cpu":
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )


def _chain_scan(
    parent: jax.Array,  # bool[W, N, N]
    exists: jax.Array,  # bool[W, N]
    leader_onehot: jax.Array,  # bool[W, N] — leader identity of slot w's round
    is_leader_slot: jax.Array,  # bool[W] — even round in (last_committed, anchor)
    anchor_slot: jax.Array,  # i32 scalar
    anchor_onehot: jax.Array,  # bool[N]
    window: int,
) -> Tuple[jax.Array, jax.Array]:
    """One descending scan = the whole ``order_leaders`` chain.

    Returns ``(committed[W], reach[W, N])``: committed[w] marks the round at
    slot w as a linked (to-commit) leader round; reach[w] is the certificate
    frontier at slot w (the causal cone of the current chain head), which
    upper-bounds the certificates ``order_dag`` can emit from that slot.
    """
    W = window

    def step(frontier, xs):
        w, parent_up, exists_w, leader_w, is_lead_w = xs
        # Certificates at slot w referenced by the frontier one round up.
        # int32 matvec: lands on the MXU for large committees, exact for bool.
        hit = (
            jnp.matmul(
                frontier.astype(jnp.int32),
                parent_up.astype(jnp.int32),
                preferred_element_type=jnp.int32,
            )
            > 0
        )
        g = hit & exists_w
        g = jnp.where(w == anchor_slot, anchor_onehot, g)
        lead_here = is_lead_w & (w < anchor_slot) & jnp.any(g & leader_w)
        # Frontier reset: the chain head becomes this leader (order_leaders'
        # ``leader = prev_leader``), so deeper reachability is from it alone.
        new_frontier = jnp.where(lead_here, g & leader_w, g)
        return new_frontier, (lead_here, g)

    slots = jnp.arange(W - 1, -1, -1, dtype=jnp.int32)
    # Step at slot w consumes parent[w+1] (edges slot w+1 → slot w).
    parent_up = jnp.concatenate(
        [parent[1:], jnp.zeros((1,) + parent.shape[1:], parent.dtype)], axis=0
    )
    xs = (
        slots,
        parent_up[slots],
        exists[slots],
        leader_onehot[slots],
        is_leader_slot[slots],
    )
    _, (committed_rev, reach_rev) = lax.scan(
        step, jnp.zeros(exists.shape[1], dtype=bool), xs
    )
    return committed_rev[::-1], reach_rev[::-1]


@partial(jax.jit, static_argnames=("window",))
def leader_chain_scan(
    parent: jax.Array,
    exists: jax.Array,
    leader_onehot: jax.Array,
    is_leader_slot: jax.Array,
    anchor_slot: jax.Array,
    anchor_onehot: jax.Array,
    window: int,
) -> Tuple[jax.Array, jax.Array]:
    """Full scan output (committed chain + per-slot reach masks) — the
    program the multichip dry-run shards (__graft_entry__.py) and the
    reach-mask consumers use."""
    return _chain_scan(
        parent, exists, leader_onehot, is_leader_slot, anchor_slot,
        anchor_onehot, window,
    )


@partial(jax.jit, donate_argnums=(0, 1))
def window_apply(
    exists: jax.Array,  # i32[W, N] counts — DONATED, updated in place
    parent: jax.Array,  # i32[W, N, N] counts — DONATED, updated in place
    ins_w: jax.Array,  # i32[C] — slot of each staged certificate
    ins_i: jax.Array,  # i32[C] — authority index of each staged certificate
    row_w: jax.Array,  # i32[C] — slot of each staged parent row
    row_c: jax.Array,  # i32[C] — child authority index of each row
    row_v: jax.Array,  # i32[C, N] — the row: 1 where the child cites parent
) -> Tuple[jax.Array, jax.Array]:
    """One batched insert flush.  The window buffers hold presence COUNTS
    (nonzero = present): scatter-ADD makes duplicate and late (waiting-
    child repair) updates order-independent, so a repair is just a one-hot
    row through the same path as a full certificate row.  Row-granular
    updates (one N-wide row per certificate, not one scatter index per
    edge) keep the XLA scatter at C indices instead of C·N.  Padding
    entries carry slot index W (out of bounds) and are dropped.  The
    buffers are donated: on device the scatter happens in place, and
    nothing returns to the host."""
    exists = exists.at[ins_w, ins_i].add(1, mode="drop")
    parent = parent.at[row_w, row_c].add(row_v, mode="drop")
    return exists, parent


@partial(jax.jit, static_argnames=("window",), donate_argnums=(0, 1))
def window_shift_op(
    exists: jax.Array,  # i32[W, N] — DONATED
    parent: jax.Array,  # i32[W, N, N] — DONATED
    d: jax.Array,  # i32 scalar — rounds to shift down (0 < d < W)
    window: int,
) -> Tuple[jax.Array, jax.Array]:
    """Shift the window down by ``d`` slots after a commit (slot 0 becomes
    the new last-committed round); vacated top slots zero-fill.  Runs as a
    donated device gather — the host never sees the buffers."""
    src = jnp.arange(window, dtype=jnp.int32) + d
    valid = src < window
    src = jnp.minimum(src, window - 1)
    exists = jnp.where(valid[:, None], exists[src], 0)
    # Slot 0 keeps no parent edges: the scan never consumes parent[0]
    # (edges point slot w → w-1), and zeroing it keeps the window an exact
    # dense rendering of the dict DAG (tests/test_reachability.py).
    keep = valid & (jnp.arange(window) > 0)
    parent = jnp.where(keep[:, None, None], parent[src], 0)
    return exists, parent


@partial(jax.jit, static_argnames=("window",))
def leader_commit_scan_counts(
    parent: jax.Array,  # i32[W, N, N] presence counts
    exists: jax.Array,  # i32[W, N] presence counts
    leader_onehot: jax.Array,
    is_leader_slot: jax.Array,
    anchor_slot: jax.Array,
    anchor_onehot: jax.Array,
    window: int,
) -> jax.Array:
    """The commit-path scan over the count-typed device window: the bool
    cast happens inside the same dispatch, and only the W-bool committed
    bitmap leaves the device — the reach masks never materialize a
    transfer, keeping the per-commit fetch at W bytes instead of W×N×N."""
    committed, _ = _chain_scan(
        parent > 0, exists > 0, leader_onehot, is_leader_slot, anchor_slot,
        anchor_onehot, window,
    )
    return committed


@partial(jax.jit, static_argnames=("window",))
def causal_mask_scan(
    parent: jax.Array,  # bool[W, N, N]
    exists: jax.Array,  # bool[W, N]
    start_slot: jax.Array,  # i32 scalar
    start_onehot: jax.Array,  # bool[N]
    window: int,
) -> jax.Array:
    """Full causal cone of one certificate: bool[W, N] mask of every
    certificate reachable from (start_slot, start_onehot) through parent
    links — the set ``order_dag`` flattens (lib.rs:263-303).  Unlike
    :func:`leader_chain_scan` the frontier accumulates (no resets)."""
    W = window

    def step(frontier, xs):
        w, parent_up, exists_w = xs
        hit = (
            jnp.matmul(
                frontier.astype(jnp.int32),
                parent_up.astype(jnp.int32),
                preferred_element_type=jnp.int32,
            )
            > 0
        )
        g = hit & exists_w
        g = g | jnp.where(w == start_slot, start_onehot, False)
        return g, g

    slots = jnp.arange(W - 1, -1, -1, dtype=jnp.int32)
    parent_up = jnp.concatenate(
        [parent[1:], jnp.zeros((1,) + parent.shape[1:], parent.dtype)], axis=0
    )
    xs = (slots, parent_up[slots], exists[slots])
    _, mask_rev = lax.scan(step, jnp.zeros(exists.shape[1], dtype=bool), xs)
    return mask_rev[::-1]


@partial(jax.jit, static_argnames=("window",))
def support_stake(
    parent: jax.Array,  # bool[W, N, N]
    exists: jax.Array,  # bool[W, N]
    stake: jax.Array,  # i32[N]
    leader_slot: jax.Array,  # i32 scalar
    leader_onehot: jax.Array,  # bool[N]
    window: int,
) -> jax.Array:
    """Stake of slot leader_slot+1 certificates referencing the leader —
    the f+1 support gate (lib.rs:141-157)."""
    child = parent[leader_slot + 1]  # bool[N, N]: child cert → its parents
    votes = jnp.any(child & leader_onehot[None, :], axis=1)
    votes = votes & exists[leader_slot + 1]
    return jnp.sum(jnp.where(votes, stake, 0))


from ..consensus.tusk import Tusk
from ..primary.messages import genesis


class KernelTusk(Tusk):
    """Tusk with ``order_leaders`` executed on device: same decisions as the
    golden Python implementation (consensus/tusk.py, validated
    certificate-for-certificate by tests/test_reachability.py), with the
    window traversals collapsed into one :func:`leader_commit_scan_counts`.  The
    emission DFS (``order_dag``) stays host-side — it is O(output) and must
    produce the exact reference DFS tie-order.

    The dense window (``exists[W, N]``, ``parent[W, N, N]``) is
    DEVICE-RESIDENT across calls.  The execution model, phase by phase:

    - **Arrival** (``insert_certificate``): O(1) — the certificate is
      appended to a host staging list.  No device dispatch, no numpy
      scatter, no digest bookkeeping; the arrival path costs the same as
      the golden Python dict insert.
    - **Commit opportunity** (``order_leaders``, reached only when the
      host-side f+1 support gate passes): the staged batch is resolved
      (digest → (round, authority) positions, out-of-order children
      repaired via the waiting-child map) and flushed to the device in
      chunked :func:`window_apply` dispatches — donated buffers, one
      static shape, padding dropped via out-of-bounds slot indices.  Then
      ONE :func:`leader_commit_scan_counts` dispatch computes the whole linked-
      leader chain, and only the W-bool committed bitmap is fetched; the
      commit sequence is reconstructed host-side from the dict DAG.
    - **Commit** (``_win_shift``): the window shifts down to the new
      ``last_committed_round`` via a donated :func:`window_shift_op`
      gather; host maps prune below the new base; certificates that
      arrived beyond the window during a stall re-stage.

    The scan runs at ONE static window shape — the smallest power of two
    covering gc_depth+2 rounds, compiled once by :meth:`prewarm` — because
    GC bounds the live DAG span to gc_depth rounds (consensus/src/lib.rs:
    56-61).  A span beyond that (only possible transiently, e.g. a commit
    stall racing GC) falls back to the golden Python walk instead of
    triggering a fresh XLA compile of a bigger shape on the consensus
    critical path."""

    def __init__(self, committee, gc_depth, fixed_coin: bool = False) -> None:
        super().__init__(committee, gc_depth, fixed_coin=fixed_coin)
        w = 8
        while w < gc_depth + 2:
            w <<= 1
        self.max_window = w
        self.python_fallbacks = 0  # observability: stalls beyond the window
        n = len(self._sorted_keys)
        self._n = n
        self._index = {name: i for i, name in enumerate(self._sorted_keys)}
        self._win_base = 0  # round held by slot 0; == last_committed_round
        # Static flush-chunk shape: a steady-state commit opportunity
        # covers ~2 rounds (≤ 2N certificates + a few repair rows), so one
        # chunk is one dispatch; a long catch-up flush loops chunks at the
        # same compiled shape.
        cap = 64
        while cap < 4 * n:
            cap <<= 1
        self._cap = cap
        # The device-resident dense window: presence COUNTS (nonzero =
        # present) so flush updates are order-independent scatter-adds.
        self._dev_exists = jnp.zeros((w, n), dtype=jnp.int32)
        self._dev_parent = jnp.zeros((w, n, n), dtype=jnp.int32)
        _silence_cpu_donation_warning()
        # Certificates staged since the last flush (arrival path is a bare
        # append; all resolution happens per commit opportunity).
        self._pending: List = []
        # digest → (absolute round, authority index), resolved at flush for
        # every certificate at or above the window base (pruned on shift)
        self._digest_pos: Dict[bytes, Tuple[int, int]] = {}
        # parent digest → [(child round, child index)]: children that
        # arrived before their parent (edge repaired on parent flush)
        self._waiting_child: Dict[bytes, List[Tuple[int, int]]] = {}
        # certificates at slots ≥ window during a stall; re-staged when a
        # commit shifts the window down far enough
        self._overflow: List = []
        self._pending.extend(genesis(committee))

    # -- arrival path: O(1) staging ------------------------------------

    def insert_certificate(self, certificate) -> None:
        super().insert_certificate(certificate)
        self._pending.append(certificate)

    def process_certificate(self, certificate) -> List:
        sequence = super().process_certificate(certificate)
        if sequence:
            self._win_shift()
        return sequence

    # -- flush: one batched dispatch per commit opportunity ------------

    def _flush_pending(self) -> None:
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        _m_flush_batch.observe(len(pending))
        # Parents (round r-1) before children (round r) within one flush;
        # cross-flush out-of-order arrivals go through the waiting map.
        pending.sort(key=lambda c: c.round)
        W = self.max_window
        n = self._n
        base = self._win_base
        digest_pos = self._digest_pos
        index = self._index
        # Each in-window certificate contributes one (slot, child, row)
        # update: its full resolved parent row.  Waiting-child repairs
        # (parent arrived in a later flush than the child) are one-hot
        # rows through the same scatter-add.
        ins_w: List[int] = []
        ins_i: List[int] = []
        rows: List[Tuple[int, int, List[int]]] = []  # (slot, child, parents)
        for cert in pending:
            r = cert.round
            if r < base:
                # Below the window (restored frontier / late straggler):
                # slot-0 certificates resolve no parent edges, so nothing
                # below base is ever referenced.
                continue
            i = index[cert.origin]
            d = cert.digest()
            digest_pos[d] = (r, i)
            w = r - base
            if w >= W:
                self._overflow.append(cert)
                continue
            ins_w.append(w)
            ins_i.append(i)
            if w >= 1:
                parents = cert.header.parents
                # Fast path: every parent already known (the overwhelmingly
                # common case — causal delivery).  The comprehension is
                # ~2× the explicit loop; stragglers take the slow path to
                # register waiting-child repairs.
                prow = [
                    pos[1]
                    for pd in parents
                    if (pos := digest_pos.get(pd)) is not None
                    and pos[0] == r - 1
                ]
                if len(prow) != len(parents):
                    for pd in parents:
                        pos = digest_pos.get(pd)
                        if pos is None or pos[0] != r - 1:
                            self._waiting_child.setdefault(pd, []).append(
                                (r, i)
                            )
                if prow:
                    rows.append((w, i, prow))
            # Repair rows for children that arrived in earlier flushes.
            for cr, ci in self._waiting_child.pop(d, ()):
                cw = cr - base
                if cr == r + 1 and 0 <= cw < W:
                    rows.append((cw, ci, [i]))
        if not ins_w and not rows:
            return
        C = self._cap
        chunks = max(-(-len(ins_w) // C), -(-len(rows) // C), 1)
        # Padding entries target slot W — out of bounds, dropped by XLA.
        iw = np.full(chunks * C, W, dtype=np.int32)
        ii = np.zeros(chunks * C, dtype=np.int32)
        iw[: len(ins_w)] = ins_w
        ii[: len(ins_i)] = ins_i
        rw = np.full(chunks * C, W, dtype=np.int32)
        rc = np.zeros(chunks * C, dtype=np.int32)
        rv = np.zeros((chunks * C, n), dtype=np.int32)
        for j, (w, i, prow) in enumerate(rows):
            rw[j] = w
            rc[j] = i
            rv[j, prow] = 1
        for k in range(chunks):
            sl = slice(k * C, (k + 1) * C)
            _m_dispatches.inc()
            self._dev_exists, self._dev_parent = window_apply(
                self._dev_exists,
                self._dev_parent,
                iw[sl],
                ii[sl],
                rw[sl],
                rc[sl],
                rv[sl],
            )

    def _win_shift(self) -> None:
        new_base = max(0, self.state.last_committed_round)
        d = new_base - self._win_base
        if d <= 0:
            return
        W = self.max_window
        if d >= W:
            # Nothing in the old window survives: fresh zero buffers beat
            # a shift dispatch.
            self._dev_exists = jnp.zeros((W, self._n), dtype=jnp.int32)
            self._dev_parent = jnp.zeros((W, self._n, self._n), dtype=jnp.int32)
        else:
            _m_shifts.inc()
            self._dev_exists, self._dev_parent = window_shift_op(
                self._dev_exists, self._dev_parent, jnp.int32(d), W
            )
        self._win_base = new_base
        # Prune host maps below the window (slot-0 certs resolve no parents).
        self._digest_pos = {
            k: v for k, v in self._digest_pos.items() if v[0] >= new_base
        }
        self._waiting_child = {
            k: kept
            for k, v in self._waiting_child.items()
            if (kept := [e for e in v if e[0] > new_base])
        }
        # Certificates that arrived beyond the window during the stall now
        # (possibly) fit: re-stage them for the next flush.
        overflow, self._overflow = self._overflow, []
        self._pending.extend(overflow)

    # -- device order_leaders ------------------------------------------

    def prewarm(self) -> None:
        """Compile (or cache-load) every kernel on the commit path —
        flush scatter, shift gather, commit scan — at their one static
        shape, off the critical path (call at node boot).  Scratch buffers
        only: the instance window is untouched."""
        n = self._n
        W = self.max_window
        C = self._cap
        e = jnp.zeros((W, n), dtype=jnp.int32)
        p = jnp.zeros((W, n, n), dtype=jnp.int32)
        iw = np.full(C, W, dtype=np.int32)
        ii = np.zeros(C, dtype=np.int32)
        rw = np.full(C, W, dtype=np.int32)
        rc = np.zeros(C, dtype=np.int32)
        rv = np.zeros((C, n), dtype=np.int32)
        e, p = window_apply(e, p, iw, ii, rw, rc, rv)
        e, p = window_shift_op(e, p, jnp.int32(1), W)
        leader_commit_scan_counts(
            p,
            e,
            np.zeros((W, n), dtype=bool),
            np.zeros((W,), dtype=bool),
            jnp.int32(0),
            np.zeros((n,), dtype=bool),
            W,
        ).block_until_ready()

    # _leader_name is inherited from Tusk (the indexed base class).

    def order_leaders(self, leader) -> List:
        state = self.state
        n = self._n
        base = max(0, state.last_committed_round)
        span = leader.round - base + 1
        window = self.max_window
        if span > window or base != self._win_base:
            self.python_fallbacks += 1
            _m_fallbacks.inc()
            return super().order_leaders(leader)

        self._flush_pending()

        leader_onehot = np.zeros((window, n), dtype=bool)
        is_leader_slot = np.zeros(window, dtype=bool)
        for r in range(leader.round - 2, state.last_committed_round, -2):
            name = self._leader_name(r)
            if state.dag.get(r, {}).get(name) is not None:
                leader_onehot[r - base, self._index[name]] = True
                is_leader_slot[r - base] = True

        anchor_onehot = np.zeros(n, dtype=bool)
        anchor_onehot[self._index[leader.origin]] = True
        # The ONLY device→host transfer on the commit path: W bools.
        committed = np.asarray(
            leader_commit_scan_counts(
                self._dev_parent,
                self._dev_exists,
                leader_onehot,
                is_leader_slot,
                jnp.int32(leader.round - base),
                anchor_onehot,
                window,
            )
        )

        # Newest-first chain, exactly as the golden order_leaders returns it.
        to_commit = [leader]
        for w in range(window - 1, -1, -1):
            if committed[w]:
                r = base + w
                _, cert = state.dag[r][self._leader_name(r)]
                to_commit.append(cert)
        return to_commit
