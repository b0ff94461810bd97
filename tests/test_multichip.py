"""The driver's hooks (``__graft_entry__``) and the multi-chip sharding of
the verify kernel: the batch axis sharded over the 8-device virtual CPU
mesh (tests/conftest.py) must give the mask of the known answers.

Hook and test are one program: the in-process case calls the hook's own
body, the subprocess case the hook as the driver calls it.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def test_sharded_verify_batch_matches_known_answers():
    """The ed25519 batch verifier data-parallel over the mesh: the batch
    axis sharded across 8 devices accepts the valid rows and rejects the
    forged and the malleable one — the multi-chip scaling story for the
    per-round crypto (one chip per primary today; batch-sharded chips per
    primary is the same program with a different mesh)."""
    from __graft_entry__ import _dryrun_multichip_impl

    assert len(jax.devices()) >= 8, "conftest must provision the 8-device CPU mesh"
    _dryrun_multichip_impl(8)


def test_entry_hands_over_the_bottom_chip_rung_as_host_arrays():
    """``entry()``: the un-jitted verify kernel and, as numpy arrays (no
    device array among them: the caller decides where they go), exactly
    what `prepare_batch` hands the kernel at the chip's bottom rung."""
    from __graft_entry__ import entry
    from narwhal_tpu.ops import ed25519 as E

    fn, args = entry()
    assert fn is E._verify_kernel.__wrapped__
    assert all(type(a) is np.ndarray for a in args)
    want = E.kernel_args(E.CHIP_RUNGS[0])
    assert [(a.shape, a.dtype) for a in args] == [(w.shape, w.dtype) for w in want]


def test_dryrun_multichip_subprocess_green():
    """The actual driver hook must run green end-to-end (it self-provisions
    a CPU mesh in a subprocess, so it works regardless of this process's
    JAX backend)."""
    from __graft_entry__ import dryrun_multichip

    dryrun_multichip(4)
