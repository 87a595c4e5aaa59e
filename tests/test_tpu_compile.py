"""What only the TPU's compiler can say, asked without a chip: programs
of the main path compiled at real shapes for a DESCRIBED v5e (libtpu is
installed here; nothing runs, so nothing here is a chip reading).

The one file for such compiles: the process that describes a topology
loads the TPU's library and keeps it, so the call is made inside a
fixture of this file, never while a module is imported (xdist hands a
file to one worker).  Where no topology can be described the tests
skip."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "w,a,b",
    [
        (100, 10240, 10240),  # a step of cidr-40k-20k-x4's ring, one direction
        (32, 512, 1024),  # a class grid
        (1, 8, 40960),
    ],
)
def test_packed_any_materializes_nothing(one_chip, w, a, b):
    """kernel.packed_any's promise "no [W, A, B] intermediate" rests on
    the compiler fusing the broadcast AND into the reduce.  On the TPU it
    does: ONE fusion, no loop, no temporary at all (the CPU backend does
    not fuse it and is no deployment target).  42 GB at the ring's shape
    if it ever stops."""
    from cyclonus_tpu.engine.kernel import packed_any

    compiled = (
        jax.jit(packed_any)
        .lower(
            jax.ShapeDtypeStruct((w, a), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((w, b), jnp.int32, sharding=one_chip),
        )
        .compile()
    )
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= a * b
    # a materialized intermediate is at least one byte a cell of [W, A, B]
    assert mem.temp_size_in_bytes < max(a * b, w * a * b // 64)
    text = compiled.as_text()
    assert " while(" not in text
    assert text.count(" fusion(") == 1
