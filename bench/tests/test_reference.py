"""The reference's copy of the Data-Scheduler search."""

from reference import noc as rnoc


def _load(m, cycles, chunk):
    return rnoc.max_load(m, rnoc.transfers(cycles, [chunk] * len(cycles)))


def test_search_never_worse_than_its_best_start():
    m = rnoc.mesh(2, 8)
    sets = ((0, 3, 9, 12), (1, 6, 10, 15), (2, 5, 8, 13), (4, 7, 11, 14))
    start = _load(m, rnoc.start_schedule(m, sets, 64.0), 64.0)
    assert rnoc.local_search(m, sets, 64.0) <= start


def test_search_finds_the_optimum_of_a_small_set():
    m = rnoc.mesh(2, 3)
    sets = ((0, 1, 2, 3, 4, 5),)
    assert rnoc.local_search(m, sets, 8.0) == rnoc.exhaustive(m, sets,
                                                               8.0)[0]
