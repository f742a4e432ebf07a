"""What `compile_scope.compiling()` does to the heap on exit
(`settle_heap`): one collection, then everything alive leaves the
collector's generations for the permanent one, so that no later
collection walks the node's long-lived state (PERF.md section 6, PR 33:
a 350-470 ms collection of the oldest generation inside a gossip phase
owned the window's tail). Plain CPython, no JAX, milliseconds."""

import gc
import weakref

import pytest

from grandine_tpu.tpu import compile_scope


class Node:
    """A container the collector tracks and a weak reference can watch."""

    def __init__(self):
        self.other = None


def tracked(obj) -> bool:
    """Whether `obj` is in one of the generations a collection walks
    (`gc.get_objects` leaves the permanent generation out)."""
    return any(o is obj for o in gc.get_objects())


def cycle():
    a, b = Node(), Node()
    a.other, b.other = b, a
    return a, weakref.ref(a)


@pytest.fixture(autouse=True)
def thawed():
    """Each case starts and ends with nothing frozen."""
    gc.unfreeze()
    yield
    gc.unfreeze()


def case_alive_at_the_exit_is_frozen():
    kept = Node()
    assert tracked(kept) and gc.get_freeze_count() == 0
    with compile_scope.compiling():
        made_inside = Node()
    assert gc.get_freeze_count() > 0
    assert not tracked(kept) and not tracked(made_inside)


def case_only_the_outermost_scope_freezes():
    kept = Node()
    with compile_scope.compiling():
        with compile_scope.compiling():
            pass
        assert tracked(kept) and gc.get_freeze_count() == 0
    assert not tracked(kept)


def case_garbage_at_the_exit_is_collected_not_frozen():
    a, dead = cycle()
    del a
    with compile_scope.compiling():
        pass
    assert dead() is None


def case_a_later_collection_walks_only_what_came_after():
    ballast = [Node() for _ in range(20_000)]
    with compile_scope.compiling():
        pass
    after = len(gc.get_objects())
    assert after < 5_000, "the frozen heap is still in the generations"
    a, dead = cycle()
    del a
    assert gc.collect() >= 2 and dead() is None  # young garbage still goes
    assert len(ballast) == 20_000


def case_a_second_exit_freezes_what_was_made_since():
    with compile_scope.compiling():
        pass
    first = gc.get_freeze_count()
    later = [Node() for _ in range(1_000)]
    assert tracked(later[0])
    with compile_scope.compiling():
        pass
    assert gc.get_freeze_count() >= first + 1_000
    assert not tracked(later[0])


def case_a_frozen_object_is_still_freed_by_its_reference_count():
    kept = Node()
    dead = weakref.ref(kept)
    with compile_scope.compiling():
        pass
    del kept
    assert dead() is None


def case_a_frozen_cycle_that_dies_waits_for_a_thaw():
    # the price, as `settle_heap` states it: chip_smoke.py's
    # release_executables thaws before it collects for this reason
    a, dead = cycle()
    with compile_scope.compiling():
        pass
    del a
    gc.collect()
    assert dead() is not None
    gc.unfreeze()
    gc.collect()
    assert dead() is None


def case_the_scope_still_counts_and_trims_when_the_body_raises():
    before = compile_scope.totals()[1]
    kept = Node()
    with pytest.raises(ZeroDivisionError):
        with compile_scope.compiling():
            1 / 0
    assert compile_scope.totals()[1] == before + 1
    assert not tracked(kept)


CASES = [value for name, value in sorted(globals().items())
         if name.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_the_heap_after_a_compile_scope(case):
    case()
