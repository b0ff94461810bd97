"""The main path's device programs compile for the real chip — checked
here, on the CPU, with the TPU's own compiler and a v5e that is described
and not attached (`on-chip-measurement` guide, section 2.3).  Nothing
runs, so this says nothing about results or times; it catches what the
chip's compiler would refuse before a chip call is spent finding out.

Everything that touches the topology lives in module-scoped fixtures of
THIS file: only one process at a time may load the TPU's library, so the
call must not happen at import (every xdist worker imports every test
file) and the programs compile in this test's own process.  One verify
shape only — it costs half a minute and the suite's time limit is shared.
"""

import os

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def lowered_verify(one_chip):
    """The driver's `entry()` (the un-jitted `_verify_kernel` at the
    bottom rung) lowered once on the described chip (~7 s of Python
    tracing and lowering): one test compiles it (~20 s), two read it."""
    from __graft_entry__ import entry

    fn, args = entry()
    abstract = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in args
    ]
    return len(args[0]), jax.jit(fn).lower(*abstract)


def test_verify_kernel_compiles_for_v5e_at_bottom_rung(lowered_verify):
    b, lowered = lowered_verify
    compiled = lowered.compile()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (b,) and out.dtype == jnp.bool_
    # (No scratch in HBM since PR 27: the whole call fits the chip's
    # vector memory, so temp_size_in_bytes reads 0.)
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_verify_kernel_names_its_phases_and_keeps_its_name(lowered_verify):
    """The four phases are named scopes in the lowered module's location
    metadata (what groups ~23,000 device operations in a profile), the
    program is still found as `_verify_kernel`, and with the metadata
    stripped (what the compile cache's key hashes) no scope name is
    left: the names cost no cold build."""
    import re

    _, lowered = lowered_verify
    with_locations = lowered.as_text(debug_info=True)
    scopes = set(re.findall(r"verify_[a-z]+", with_locations))
    assert scopes >= {
        "verify_decompress", "verify_table", "verify_ladder", "verify_compare",
    }, scopes
    plain = lowered.as_text()
    assert "module @jit__verify_kernel" in plain
    assert not re.search(
        r"verify_(decompress|table|ladder|compare)", plain
    )


def test_entry_lowers_to_the_program_a_backend_builds(lowered_verify):
    """What the hook lowers is what `verify_program` builds for the
    bottom chip rung: the same function at the same nine abstract
    arrays, so the module above IS the node's program."""
    from narwhal_tpu.ops import ed25519 as E

    b, lowered = lowered_verify
    assert b == E.CHIP_RUNGS[0]
    got, _ = lowered.in_avals
    assert [(a.shape, a.dtype) for a in got] == [
        (w.shape, w.dtype) for w in E.kernel_args(b)
    ]
    assert "module @jit__verify_kernel" in lowered.as_text()
