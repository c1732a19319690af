"""The measured window holds whole cycles of the traffic's sizes: the
generator's cycle covers the mixture from any start, a window whose
``seconds`` run out mid-cycle runs on to the cycle's end, and a window of
``iterations`` runs exactly that many. The harness skips its look for a
chip here."""
import time

import numpy as np
import pytest

from bench import catalog, harness

COT = catalog.load_traffic("cot-p256")
GEN = catalog.traffic_generator(COT)


@pytest.mark.parametrize("start", [0, 1, 2, 3, 5, 22, 1021, 2**20 + 7])
def test_any_cycle_of_cot_holds_one_point_per_quarter(start):
    cycle = GEN.cycle(COT)
    assert cycle == 4
    points = [GEN.van_der_corput(i) for i in range(start, start + cycle)]
    assert sorted(int(p * 4) for p in points) == [0, 1, 2, 3]
    # so the cycle's n rows per iteration sit one in each of 4 * n strata
    n = COT["group_size"]  # rows of one prompt per iteration, as the cell
    levels = np.concatenate([GEN.stratified(n, p) for p in points])
    assert sorted((levels * cycle * n).astype(int)) == list(range(cycle * n))


class _SlowAt:
    """The pipeline's ``run``, with one iteration made to last ``seconds``
    longer."""

    def __init__(self, run, at: int, seconds: float):
        self._run, self._at, self._seconds = run, at, seconds
        self.calls = 0

    def __call__(self, n):
        out = self._run(n)
        self.calls += 1
        if self.calls == self._at:
            time.sleep(self._seconds)
        return out


@pytest.fixture(scope="module")
def session():
    from conftest import tiny_cell

    sess = harness.Session(tiny_cell("dense"), 2**31 + 41)
    sess.window(0, iterations=1)  # compiles outside the windows below
    return sess


@pytest.mark.parametrize("slow_at,want", [(2, 4), (5, 8)])
def test_window_crossed_mid_cycle_runs_to_the_cycles_end(session, slow_at,
                                                         want):
    seconds = 2.0
    run = session.pipe.run
    session.pipe.run = slow = _SlowAt(run, slow_at, seconds)
    try:
        window_s, counts = session.window(seconds)
    finally:
        session.pipe.run = run
    assert counts["iterations"] == slow.calls == want
    assert len(counts["iteration_s"]) == len(counts["iteration_tokens"]) == want
    assert counts["iteration_s"][slow_at - 1] >= seconds
    assert sum(counts["iteration_s"]) <= window_s
    assert all(t > 0 for t in counts["iteration_tokens"])


@pytest.mark.parametrize("k", [1, 2, 3])
def test_a_window_of_k_iterations_runs_exactly_k(session, k):
    _, counts = session.window(0, iterations=k)
    assert counts["iterations"] == len(counts["iteration_s"]) == k


def test_collector_pauses_are_logged_by_iteration_and_generation(session):
    import gc
    import io

    with harness.GcPauses() as pauses:
        gc.collect(2)
        gc.collect(0)
    assert [p[0] for p in pauses.pauses] == [2, 0]
    assert all(dt >= 0 for _, _, dt in pauses.pauses)
    assert gc.callbacks.count(pauses) == 0

    _, counts = session.window(0, iterations=2)
    counts["gc_pauses"] = [(1, 2, 0.25), (1, 0, 0.5), (2, 2, 0.125)]
    out = io.StringIO()
    harness.print_iterations(counts, stream=out)
    lines = out.getvalue().splitlines()
    assert len(lines) == 3 and all(s.startswith("bench: ") for s in lines)
    assert lines[0].endswith("gc 0.000000 s")
    assert lines[1].endswith("gc 0.750000 s")
    assert lines[2] == ("bench: gc pauses in the window: generation 0 1 for "
                        "0.500000 s, generation 2 2 for 0.375000 s")
