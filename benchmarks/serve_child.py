#!/usr/bin/env python3
"""The served child: `python -m cyclonus_tpu serve <arguments>` through the
CLI's own `main`, in this process, with one addition: at exit it says on
stderr what JAX reports of the device and its peak memory, because the program
has no metric for device memory and only the process that holds the chip can
read it.  Nothing else is wrapped, patched or timed here.
"""

import atexit
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MARK = "bench-child-device: "


def _report() -> None:
    import jax

    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    print(MARK + json.dumps({
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(peak),
    }), file=sys.stderr, flush=True)


if __name__ == "__main__":
    from cyclonus_tpu.cli import main

    atexit.register(_report)
    sys.exit(main(["serve", *sys.argv[1:]]))
