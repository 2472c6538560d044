"""The shared continuous-batching core (serving/batching.py) in isolation:
slot-ladder selection, the block pool's aliasing-safety contract, the
dispatch loop's ordering/padding/in-flight behaviour and its
all-or-nothing commit/rollback semantics, and the admission/fairness
primitives the fleet-scale monitor builds on.  The engine- and server-level
suites (test_streaming_engine.py, test_serve.py, test_fault_tolerance.py)
cover the same core through its two production callers.
"""
import numpy as np
import pytest

from repro.serving.batching import (
    AdmissionPolicy,
    BlockPool,
    DispatchCore,
    SlotPolicy,
    fair_allocation,
)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st


# ---------------------------------------------------------------------------
# SlotPolicy
# ---------------------------------------------------------------------------


def test_fixed_policy_always_max():
    p = SlotPolicy.fixed(8)
    assert p.ladder == (8,)
    for backlog in (1, 3, 8, 100):
        assert p.pick(backlog) == 8


def test_adaptive_ladder_powers_of_two():
    p = SlotPolicy(8, adaptive=True)
    assert p.ladder == (1, 2, 4, 8)
    assert p.pick(1) == 1
    assert p.pick(2) == 2
    assert p.pick(3) == 2  # largest that fits: 2, then a 1-block follows
    assert p.pick(7) == 4
    assert p.pick(8) == 8
    assert p.pick(1000) == 8


def test_adaptive_ladder_respects_min_slots():
    p = SlotPolicy(16, adaptive=True, min_slots=4)
    assert p.ladder == (4, 8, 16)
    # sub-min backlog dispatches the smallest ladder block (bounded padding)
    assert p.pick(1) == 4
    assert p.pick(5) == 4
    assert p.pick(16) == 16


def test_adaptive_ladder_multiple_for_shards():
    p = SlotPolicy(8, adaptive=True, multiple=2)
    assert p.ladder == (2, 4, 8)
    assert all(s % 2 == 0 for s in p.ladder)
    assert p.pick(1) == 2  # never dispatches a shape the mesh can't split


def test_slot_policy_validation():
    with pytest.raises(ValueError, match="max_slots"):
        SlotPolicy(0)
    with pytest.raises(ValueError, match="min_slots"):
        SlotPolicy(4, min_slots=5)
    with pytest.raises(ValueError, match="multiple"):
        SlotPolicy(6, multiple=4)
    with pytest.raises(ValueError, match="backlog"):
        SlotPolicy(4).pick(0)


def test_adaptive_total_padding_bounded_by_ladder():
    # whatever the backlog, padding only ever occurs on the final sub-min
    # block, so it is < the smallest ladder value
    p = SlotPolicy(8, adaptive=True)
    for backlog in range(1, 40):
        remaining, padded = backlog, 0
        while remaining > 0:
            s = p.pick(remaining)
            live = min(s, remaining)
            padded += s - live
            remaining -= live
        assert padded == 0  # ladder reaches down to 1: never pads


# ---------------------------------------------------------------------------
# BlockPool
# ---------------------------------------------------------------------------


def test_block_pool_rotation_depth():
    """Each block handed out since the last rewind has a buffer of its own;
    after ``rewind`` the pool hands the same buffers out again, in order,
    and grows only when a round holds more blocks than any before it."""
    pool = BlockPool(width=3)
    rows = [np.full(3, i, np.float32) for i in range(10)]
    b0 = pool.pack(rows[:2], 4)
    b1 = pool.pack(rows[2:4], 4)
    b2 = pool.pack(rows[4:6], 4)
    assert b0 is not b1 and b1 is not b2 and b0 is not b2
    pool.rewind()
    assert pool.pack(rows[6:8], 4) is b0
    assert pool.pack(rows[8:10], 4) is b1
    assert pool.pack(rows[:1], 4) is b2
    b3 = pool.pack(rows[1:2], 4)  # a deeper round: one more buffer
    assert all(b3 is not b for b in (b0, b1, b2))


@pytest.mark.parametrize("k", [1, 2, 5, 16])
def test_block_pool_outstanding_blocks_never_alias(k):
    """With k blocks of one shape outstanding (handed out, not rewound),
    no buffer handed out aliases any of the k — also while another shape's
    blocks are handed out in between, and again in the next round."""
    pool = BlockPool(width=4)
    for _ in range(2):  # a second round reuses the first round's buffers
        pool.rewind()
        held = []
        for j in range(k):
            held.append(pool.buffer(8, 8))
            other = pool.buffer(2, 1)  # another shape: its own buffers
            assert not any(np.shares_memory(other, h) for h in held)
        for a in range(k):
            for b in range(a + 1, k):
                assert not np.shares_memory(held[a], held[b])


def test_block_pool_zeroes_dead_tail():
    pool = BlockPool(width=2)
    full = pool.pack([np.ones(2, np.float32)] * 3, 3)
    np.testing.assert_array_equal(full, np.ones((3, 2), np.float32))
    pool.rewind()
    partial = pool.pack([np.full(2, 7.0, np.float32)], 3)
    assert partial is full
    np.testing.assert_array_equal(partial[0], np.full(2, 7.0, np.float32))
    np.testing.assert_array_equal(partial[1:], np.zeros((2, 2), np.float32))


def test_block_pool_buffer_shares_the_rotation_and_zeroes_the_dead_tail():
    """``buffer`` hands out the same buffers as ``pack``, for a caller that
    fills the live rows itself: a reused buffer's dead tail is silence
    again, its live rows are left to the caller."""
    pool = BlockPool(width=2)
    a = pool.pack([np.full(2, 5.0, np.float32)] * 3, 3)
    b = pool.buffer(3, 3)
    b[:] = 9.0
    assert b is not a
    pool.rewind()
    again = pool.buffer(3, 1)
    assert again is a  # the next round starts from the first buffer
    np.testing.assert_array_equal(again[0], np.full(2, 5.0, np.float32))
    np.testing.assert_array_equal(again[1:], np.zeros((2, 2), np.float32))
    assert pool.pack([np.ones(2, np.float32)], 3) is b
    with pytest.raises(ValueError, match="do not fit"):
        pool.buffer(3, 4)


def test_block_pool_shapes_rotate_independently():
    pool = BlockPool(width=1)
    a = pool.pack([np.zeros(1, np.float32)], 2)
    b = pool.pack([np.zeros(1, np.float32)], 4)  # other shape: fresh pool
    c = pool.pack([np.ones(1, np.float32)], 2)
    assert a.shape == (2, 1) and b.shape == (4, 1)
    assert a is not c  # shape 2 handed out its second buffer
    pool.rewind()
    assert pool.pack([np.zeros(1, np.float32)], 4) is b  # untouched by shape 2
    with pytest.raises(ValueError, match="do not fit"):
        pool.pack([np.zeros(1, np.float32)] * 3, 2)


# ---------------------------------------------------------------------------
# DispatchCore
# ---------------------------------------------------------------------------


def _sync_core(slots=4, adaptive=False, **kw):
    """Core over a synchronous 'program' that records each block."""
    calls = []

    def submit(live, n_slots):
        calls.append((list(live), n_slots))
        return [x * 10 for x in live]

    core = DispatchCore(
        submit=submit,
        harvest=None,
        slot_policy=SlotPolicy(slots, adaptive=adaptive),
        **kw,
    )
    return core, calls


def test_dispatch_preserves_input_order_and_chunks():
    core, calls = _sync_core(slots=4)
    out = core.dispatch(list(range(10)))
    assert out == [x * 10 for x in range(10)]
    assert [n for _, n in calls] == [4, 4, 4]
    assert core.blocks_dispatched == 3
    assert core.padded_slots == 2  # final block: 2 live in 4 slots
    assert core.slot_histogram == {4: 3}
    assert core.depth_histogram == {}  # synchronous: nothing in flight


def test_dispatch_adaptive_shrinks_tail():
    core, calls = _sync_core(slots=4, adaptive=True)
    out = core.dispatch(list(range(7)))
    assert out == [x * 10 for x in range(7)]
    assert [n for _, n in calls] == [4, 2, 1]
    assert core.padded_slots == 0
    assert core.slot_histogram == {4: 1, 2: 1, 1: 1}


def _async_core(slots=2, adaptive=False, fail_at=None, **kw):
    """Core over an async 'program': ``submit`` returns a handle, nothing
    is computed until ``harvest``.  ``events`` records both in order;
    ``fail_at = ("submit" | "harvest", k)`` raises at the k-th (1-based)
    call of that hook, once."""
    events, in_flight, calls = [], [], {"submit": 0, "harvest": 0}

    def hook_called(name):
        calls[name] += 1
        if fail_at == (name, calls[name]):
            raise RuntimeError("injected")

    def submit(live, n_slots):
        events.append(("submit", list(live)))
        hook_called("submit")
        handle = [x + 100 for x in live]
        in_flight.append(handle)
        return handle

    def harvest(handle):
        events.append(("harvest", handle))
        in_flight.remove(handle)
        hook_called("harvest")
        return handle

    core = DispatchCore(
        submit=submit, harvest=harvest,
        slot_policy=SlotPolicy(slots, adaptive=adaptive), **kw,
    )
    return core, events, in_flight


@pytest.mark.parametrize(
    "n, slots, adaptive, blocks",
    [(9, 2, False, 5), (1, 2, False, 1), (7, 4, True, 3), (64, 8, False, 8)],
)
def test_async_harvest_bounded_inflight(n, slots, adaptive, blocks):
    """The in-flight depth is the round's own block count: every block is
    submitted before the first harvest, the blocks are harvested in order,
    and the results come back in input order."""
    core, events, in_flight = _async_core(slots, adaptive)
    out = core.dispatch(list(range(n)))
    assert out == [x + 100 for x in range(n)]
    kinds = [e[0] for e in events]
    assert kinds == ["submit"] * blocks + ["harvest"] * blocks
    submitted = [[x + 100 for x in e[1]] for e in events[:blocks]]
    assert [e[1] for e in events[blocks:]] == submitted  # harvest in order
    assert not in_flight  # everything harvested by the end
    assert core.depth_histogram == {blocks: 1}
    core.dispatch(list(range(n)))
    assert core.depth_histogram == {blocks: 2}


@pytest.mark.parametrize("fail_at", [("submit", 4), ("harvest", 2)])
def test_failed_block_waits_for_blocks_in_flight_and_retries(fail_at):
    """A failure at the 4th of 5 submits, or at the 2nd harvest with every
    block submitted, is raised only after each block still in flight was
    waited on; their results are dropped, nothing is committed, the
    rollback fires, and the retried round returns what a clean one does."""
    committed, rolled = [], []
    core, events, in_flight = _async_core(
        2, fail_at=fail_at,
        on_commit=lambda items, res: committed.append(list(res)),
        on_rollback=lambda items: rolled.append(list(items)),
    )
    items = list(range(9))
    with pytest.raises(RuntimeError, match="injected"):
        core.dispatch(items)
    assert not in_flight  # every submitted block was waited on
    assert committed == [] and rolled == [items]
    kinds = [e[0] for e in events]
    if fail_at[0] == "submit":
        # 3 blocks in flight, the 4th submit raised: the 3 are harvested
        assert kinds == ["submit"] * 4 + ["harvest"] * 3
        assert core.depth_histogram == {}  # no round reached a harvest
    else:
        assert kinds == ["submit"] * 5 + ["harvest"] * 5
        assert core.depth_histogram == {5: 1}
    events.clear()
    assert core.dispatch(items) == [x + 100 for x in items]
    assert committed == [[x + 100 for x in items]]
    assert [e[0] for e in events] == ["submit"] * 5 + ["harvest"] * 5


def test_enqueue_drain_fifo_and_requeue_on_failure():
    boom = {"armed": True}

    def submit(live, slots):
        if boom["armed"]:
            raise RuntimeError("injected")
        return list(live)

    core = DispatchCore(
        submit=submit, harvest=None, slot_policy=SlotPolicy(3)
    )
    core.enqueue([1, 2, 3, 4])
    with pytest.raises(RuntimeError, match="injected"):
        core.drain()
    # rollback: the items went back to the front of the queue, in order
    core.enqueue([5])
    boom["armed"] = False
    assert core.drain() == [1, 2, 3, 4, 5]
    assert core.drain() == []  # empty queue: no dispatch


def test_pre_dispatch_seam_fires_before_submit_and_rolls_back():
    events = []

    def pre(items):
        events.append(("pre", list(items)))
        raise RuntimeError("injected crash")

    core = DispatchCore(
        submit=lambda live, n: events.append(("submit", list(live))) or list(live),
        harvest=None,
        slot_policy=SlotPolicy(2),
        pre_dispatch=pre,
        on_rollback=lambda items: events.append(("rollback", list(items))),
    )
    with pytest.raises(RuntimeError, match="injected crash"):
        core.dispatch([1, 2, 3])
    assert events == [("pre", [1, 2, 3]), ("rollback", [1, 2, 3])]
    core.pre_dispatch = None
    assert core.dispatch([1, 2]) is not None  # seam cleared: dispatch works


def test_on_commit_sees_items_and_results():
    committed = []
    core = DispatchCore(
        submit=lambda live, n: [x * 2 for x in live],
        harvest=None,
        slot_policy=SlotPolicy(2),
        on_commit=lambda items, results: committed.append((items, results)),
    )
    core.dispatch([1, 2, 3])
    assert committed == [([1, 2, 3], [2, 4, 6])]


def test_mid_stream_failure_rolls_back_without_partial_commit():
    # a failure on block 2 must not fire on_commit even though block 1
    # already returned results — all-or-nothing from the caller's view
    committed, rolled = [], []

    def submit(live, slots):
        if live[0] >= 2:
            raise RuntimeError("late failure")
        return list(live)

    core = DispatchCore(
        submit=submit, harvest=None, slot_policy=SlotPolicy(2),
        on_commit=lambda *a: committed.append(a),
        on_rollback=lambda items: rolled.append(list(items)),
    )
    with pytest.raises(RuntimeError, match="late failure"):
        core.dispatch([0, 1, 2, 3])
    assert committed == []
    assert rolled == [[0, 1, 2, 3]]


# ---------------------------------------------------------------------------
# AdmissionPolicy / fair_allocation
# ---------------------------------------------------------------------------


def test_admission_policy_validation():
    AdmissionPolicy()  # defaults valid
    with pytest.raises(ValueError, match="max_streams"):
        AdmissionPolicy(max_streams=0)
    with pytest.raises(ValueError, match="max_per_stream_per_round"):
        AdmissionPolicy(max_per_stream_per_round=0)
    with pytest.raises(ValueError, match="round_budget"):
        AdmissionPolicy(round_budget=0)
    with pytest.raises(ValueError, match="evict_overflow_rounds"):
        AdmissionPolicy(evict_overflow_rounds=0)


def test_fair_allocation_passthrough_when_budget_covers():
    want = np.array([3, 0, 2, 1])
    np.testing.assert_array_equal(fair_allocation(want, None), want)
    np.testing.assert_array_equal(fair_allocation(want, 6), want)
    np.testing.assert_array_equal(fair_allocation(want, 100), want)


def test_fair_allocation_depth_fair_under_pressure():
    # firehose stream 0 wants 10, trickles want 1 each; budget 4 must give
    # every wanting stream its first window before stream 0's second
    want = np.array([10, 1, 1, 1])
    np.testing.assert_array_equal(fair_allocation(want, 4), [1, 1, 1, 1])
    # one more unit of budget goes to the deepest demand, stream 0
    np.testing.assert_array_equal(fair_allocation(want, 5), [2, 1, 1, 1])


def test_fair_allocation_ties_break_by_index():
    want = np.array([2, 2, 2])
    np.testing.assert_array_equal(fair_allocation(want, 4), [2, 1, 1])
    np.testing.assert_array_equal(fair_allocation(want, 2), [1, 1, 0])


def test_fair_allocation_rejects_negative():
    with pytest.raises(ValueError, match="non-negative"):
        fair_allocation(np.array([1, -1]), 2)


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.integers(min_value=0, max_value=9), min_size=1, max_size=12),
    st.integers(min_value=1, max_value=40),
)
def test_fair_allocation_properties(want, budget):
    want = np.asarray(want, np.int64)
    alloc = fair_allocation(want, budget)
    # never over-serves a stream, never exceeds the budget
    assert (alloc <= want).all() and (alloc >= 0).all()
    assert alloc.sum() <= budget
    # work-conserving: either demand is fully met or the budget is spent
    assert alloc.sum() == min(int(want.sum()), budget)
    # depth-fairness: a stream only reaches depth d+1 once every stream
    # wanting depth d got it (up to the index tie-break at the boundary)
    if (want > 0).any():
        served = alloc[want > 0]
        assert served.max() - served.min() <= 1 or served.min() >= 1
