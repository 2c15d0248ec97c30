"""GL116 seed: device dispatch primitives without a ledger class.

Three violations; the tagged/aware forms below them must stay clean."""
from seaweedfs_tpu.obs import devledger


def bare_dispatch(vec, a_prep, survivors):
    # GL116: busy time lands in the `untagged` ledger class
    return _dispatch_call("xla", vec, a_prep, survivors)  # noqa: F821


def bare_bulk_leg(tpu, a_bm, x):
    return tpu.apply_matrix_device_flat(a_bm, x, k=4, m=2)  # GL116


def closure_is_not_tagged_by_its_build_site(a_bm, data, parity):
    with devledger.workload("scrub"):
        def thunk():
            # GL116: dispatched later — the with above does not cover it
            return _scrub_call(  # noqa: F821
                a_bm, data, parity, n_lanes=128
            )
    return thunk


def tagged_with_workload(vec, a_prep, survivors):
    with devledger.workload("ingest"):
        return _dispatch_call("xla", vec, a_prep, survivors)  # noqa: F821


def tagged_with_device(vec, a_prep, survivors):
    with devledger.device("mesh"):
        return _dispatch_call(  # noqa: F821
            "sharded", vec, a_prep, survivors
        )


def tagged_by_kwarg(codec, shards):
    return codec.apply_matrix_device_flat(shards, workload="bulk")  # clean


def attribution_aware_by_param(vec, a_prep, survivors, workload):
    # clean: the class rides as a parameter (bulk.py Codec legs pattern)
    return _dispatch_call("xla", vec, a_prep, survivors)  # noqa: F821


def attribution_aware_by_consult(a_blk, flat):
    if devledger.current_workload() == "scrub":
        return _scrub_all_call(a_blk, flat, vols=2)  # noqa: F821
    return _scrub_call_blockdiag(a_blk, flat, groups=8)  # noqa: F821


def waived_probe(vec, a_prep, survivors):
    # graftlint: allow(untagged-device-dispatch): a probe's measured
    # region — timed externally, deliberately unattributed
    return _dispatch_call("xla", vec, a_prep, survivors)  # noqa: F821
