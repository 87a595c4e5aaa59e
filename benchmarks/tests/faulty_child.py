#!/usr/bin/env python3
"""A serve child whose timed path is broken underneath: every reply's first
verdict has its Combined bit flipped where the service produces it.  Only
test_correct.py starts it, to see `correct` come out false."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import serve_child  # noqa: E402

if __name__ == "__main__":
    import atexit

    from cyclonus_tpu.cli import main
    from cyclonus_tpu.serve.service import VerdictService

    query = VerdictService.query

    def altered(self, queries):
        out = query(self, queries)
        if out:
            out[0].combined = not out[0].combined
        return out

    VerdictService.query = altered
    atexit.register(serve_child._report)
    sys.exit(main(["serve", *sys.argv[1:]]))
