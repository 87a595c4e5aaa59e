"""The comparison that decides `correct` for the in-process kinds.

Inside the window it only keeps what the timed path returned: for the counts
entry every request's integers; for the tables entry, of every request a
sample of cells drawn from the seed, and of each distinct request (a case set,
a policy set) the whole tables of its last occurrence.  After the window, with
the program's state freed, `compare` holds all of it to reference.py:

  tables  `table_cells_wrong`    cells of the kept whole tables that differ
          `sampled_cells_wrong`  sampled cells of all requests that differ
  counts  `count_requests_wrong` requests any of whose four integers differs

Every comparison is exact, so every limit is 0 (PERF.md gives the readings).
`substitute` puts another answerer in the program's place: the control.
"""

import numpy as np

SAMPLES = 64


class GridChecker:
    def __init__(self, result: str, n_pods: int, rng):
        self.result = result
        self.n = n_pods
        self.np_rng = np.random.default_rng(rng.getrandbits(63))
        self.kept = {}      # request key -> the whole last answer
        self.sampled = []   # (key, q, s, d, values[K, 3])
        self.counts = []    # (key, dict)

    def record(self, key, n_cases: int, answer) -> None:
        if self.result == "counts":
            self.counts.append((key, answer))
            return
        q = self.np_rng.integers(0, n_cases, SAMPLES)
        s = self.np_rng.integers(0, self.n, SAMPLES)
        d = self.np_rng.integers(0, self.n, SAMPLES)
        self.sampled.append((key, q, s, d, _cells(answer, q, s, d)))
        self.kept[key] = answer

    def substitute(self, answer_of) -> None:
        """Replace what was kept by what `answer_of(key)` would have returned."""
        if self.result == "counts":
            said = {k: answer_of(k) for k in {k for k, _ in self.counts}}
            self.counts = [(k, said[k]) for k, _ in self.counts]
            return
        for key in list(self.kept):
            tables = answer_of(key)
            self.kept[key] = tables
            self.sampled = [
                (k, q, s, d, _cells(tables, q, s, d) if k == key else v)
                for k, q, s, d, v in self.sampled
            ]

    def compare(self, expected_of) -> list:
        """[(name, value, limit)] against `expected_of(key)`, one key at a
        time so that one reference answer is alive at once."""
        if self.result == "counts":
            want = {k: expected_of(k) for k in {k for k, _ in self.counts}}
            wrong = sum(1 for k, got in self.counts if got != want[k])
            return [("count_requests_wrong", wrong, 0)]
        cells_wrong = sampled_wrong = 0
        for key in sorted(self.kept):
            want = expected_of(key)
            for got, ref in zip(self.kept.pop(key), want):
                cells_wrong += (
                    int(np.count_nonzero(got != ref))
                    if got.shape == ref.shape else ref.size
                )
            for k, q, s, d, values in self.sampled:
                if k == key:
                    sampled_wrong += int(np.count_nonzero(values != _cells(want, q, s, d)))
        return [("table_cells_wrong", cells_wrong, 0),
                ("sampled_cells_wrong", sampled_wrong, 0)]


def _cells(tables, q, s, d) -> np.ndarray:
    """[K, 3] of (ingress[q, d, s], egress[q, s, d], combined[q, s, d])."""
    ingress, egress, combined = tables
    return np.stack([ingress[q, d, s], egress[q, s, d], combined[q, s, d]], axis=1)
