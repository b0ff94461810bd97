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

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: the next run would warn
    # and compile again.  Off around these tests.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def lowered_verify(one_chip):
    """`_verify_kernel` lowered once for the bottom rung on the described
    chip (~7 s of Python tracing and lowering): one test compiles it
    (~20 s), one reads it."""
    from narwhal_tpu.ops import ed25519 as E

    b = E.CHIP_RUNGS[0]
    return b, E._verify_kernel.lower(*E.kernel_args(b, sharding=one_chip))


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


def test_commit_step_compiles_for_v5e_at_n50(one_chip):
    from __graft_entry__ import commit_fixture, make_commit_step

    window, n = 64, 50
    args = [
        jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=one_chip)
        for a in commit_fixture(0, window, n)
    ]
    args[5] = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_commit_step(window)).lower(*args).compile()
    support, committed, reach = compiled.out_info
    assert reach.shape == (window, n)
    assert compiled.memory_analysis() is not None
