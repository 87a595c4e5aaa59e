"""The chip's published peaks, and the least bytes a verdict grid must move.

PEAKS is keyed by `device_kind` as JAX reports it.  Source: Google Cloud
documentation, "TPU v5e" (one chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM at 819 GB/s).  A kind that is not in the table is an error, not a default.
There is no guessed vector-unit rate here (bench.roofline_model's
`vpu_ops: 4e12` was one).

`grid_min_bytes` is the numerator of `kernel.grid_roofline`.  It reads the
cell's SHAPES alone - pods, policies, port cases, the form of the result - and
nothing the engine chose (executed blocks, class counts, tile sizes, tensor
layouts), so it reads the same work whatever implements it:

  read once   the cluster, at POD_BYTES a pod (a namespace id, three label
              ids and an IPv4 address as 32-bit words: 20 bytes), and the
              policy set, at RULE_BYTES a policy direction (namespace id,
              target label, peer selector or CIDR with one except, two port
              specs: 48 bytes); both byte counts are fixed HERE and are not
              the sizes of the engine's tensors
  write once  the result in its densest exact form: one bit a verdict for the
              three tables (3 x Q x N x N / 8 bytes), or the counts
              (ingress, egress, combined, cells as 64-bit integers: 32 bytes)

The least time is those bytes over the HBM peak; the share is that over the
measured device time of a request.  It deliberately does NOT count the dense
boolean-matmul operations (2 x N^2 x Q x T): namespace block skip and class
compression legitimately avoid them, and counting them reads far above 100 %
at 100,000 x 10,000 (5.6e14 operations would need 2.8 s at the int8 peak; a
request takes a fraction of that).  So the share is a LOOSE bound, small by
construction (well under 1 % on the counts cell): no tighter one is independent
of the implementation, because the work above the bytes is exactly what an
implementation is free to avoid.
"""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

POD_BYTES = 20
RULE_BYTES = 48
COUNTS_BYTES = 32


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def grid_min_bytes(pods: int, policies: int, port_cases: int, result: str) -> int:
    """Bytes any exact implementation moves for one request of the cell."""
    read = pods * POD_BYTES + 2 * policies * RULE_BYTES
    if result == "tables":
        write = 3 * port_cases * pods * pods // 8
    elif result == "counts":
        write = COUNTS_BYTES
    else:
        raise ValueError(f"unknown result form {result!r}")
    return read + write


def grid_min_seconds(device_kind: str, **shape) -> float:
    return grid_min_bytes(**shape) / peaks_for(device_kind)["hbm_bytes_per_s"]
