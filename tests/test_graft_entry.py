"""`__graft_entry__.entry()` under tier-1: the jitted single-chip encode
step against the host codec.  `dryrun_multichip` runs 80 MB over the
mesh and is not a tier-1 test (`python __graft_entry__.py --dryrun 8`).
"""
import jax
import numpy as np

import __graft_entry__ as graft
from seaweedfs_tpu.ops import gf256, rs_cpu


def test_entry_matches_host_codec():
    fn, (a_bm, x) = graft.entry()
    parity = np.asarray(jax.jit(fn)(a_bm, x))
    want = rs_cpu.apply_matrix_numpy(
        gf256.parity_matrix(10, 14), np.asarray(x)
    )
    assert parity.shape == (4, x.shape[1]) and parity.dtype == np.uint8
    np.testing.assert_array_equal(parity, want)
