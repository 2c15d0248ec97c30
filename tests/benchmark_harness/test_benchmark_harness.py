"""The benchmark's own tests (BENCHMARK.json `paths`): its arithmetic on
hand-worked cases, its trace reduction on a synthetic trace, its files
found by name, and both cells rehearsed end to end on the CPU at a few
MiB — once sound, once with the control's stale shard, once with each
fault planted under the timed path, which has to come out `correct:
false`.

No JAX at import, no topology call anywhere: the rehearsals run
benchmark/run.py as a child process the way the driver does.
"""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import declarations  # noqa: E402
from benchmark import metrics_eval, peaks, trace, work_counts  # noqa: E402
from benchmark.cluster import parse_metrics, series_sum  # noqa: E402
from benchmark.dataset import read_index  # noqa: E402
from benchmark.generators.closed_loop_get import percentile, pick_pool  # noqa: E402
from benchmark.reference import rs_plain  # noqa: E402
from declarations import GET_CELL, UNIT  # noqa: E402

BENCH = declarations.load(REPO)
BULK_CELL = "ec-bulk-1g.encode-rebuild"


# ------------------------------------------------------- BENCHMARK.json


def test_benchmark_json_names_units_and_files():
    declarations.check_names_units_and_files(BENCH, REPO)


# ------------------------------------------------------ work_counts, peaks


def test_work_counts_on_hand_worked_cases():
    assert work_counts.reconstruct_bytes(1000) == 11_000
    assert work_counts.encode_bytes(10 << 20) == 14 << 20
    assert work_counts.rebuild_bytes(1 << 20, 2) == 12 << 20
    mib = 1 << 20
    # block 3 of the .dat is row 0 of shard 3, block 13 is its row 1
    assert work_counts.bytes_on_shard(3 * mib, mib, 3) == mib
    assert work_counts.bytes_on_shard(3 * mib - 100, 300, 3) == 200
    assert work_counts.bytes_on_shard(3 * mib - 100, 300, 2) == 100
    assert work_counts.bytes_on_shard(0, 20 * mib, 3) == 2 * mib
    assert work_counts.bytes_on_shard(4 * mib, 9 * mib, 3) == 0
    assert work_counts.bytes_on_shard(4 * mib, 9 * mib + 5, 3) == 5
    # 819 bytes at 819 GB/s take 1 ns: done in 4 ns is a quarter
    assert work_counts.roofline_pct(819, 4e-9, 819e9) == pytest.approx(25.0)
    # nothing to read is None, never 0
    assert work_counts.roofline_pct(819, 0.0, 819e9) is None
    assert work_counts.roofline_pct(0, 1.0, 819e9) is None


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(peaks.UnknownDevice):
            peaks.lookup(kind)


# ------------------------------------------------------- plain reference


def test_plain_reference_is_the_upstream_code():
    m = rs_plain.coding_matrix()
    assert [row[:10] for row in m[:10]] == [
        [int(i == j) for j in range(10)] for i in range(10)]
    # klauspost/reedsolomon's first parity row for (10, 4)
    assert m[10] == [129, 150, 175, 184, 210, 196, 254, 232, 3, 2]
    for a in (1, 2, 87, 255):
        assert rs_plain.gf_mul(a, rs_plain.gf_inv(a)) == 1
    # second witness: the program's host codec, on random bytes
    from seaweedfs_tpu.ops import gf256, rs_cpu
    assert np.array_equal(np.array(m, dtype=np.uint8),
                          np.asarray(gf256.build_matrix(10, 14)))
    data = np.random.default_rng(5).integers(
        0, 256, (10, 4096), dtype=np.uint8)
    got = rs_plain.apply_rows(m[10:], data)
    assert np.array_equal(got, rs_cpu.apply_matrix_numpy(
        np.asarray(m[10:], dtype=np.uint8), data))
    # any 10 of the 14 shards give the data back
    full = np.concatenate([data, got])
    keep = [0, 1, 2, 4, 5, 6, 7, 8, 9, 12]
    inv = rs_plain.mat_inv([m[i] for i in keep])
    assert np.array_equal(rs_plain.apply_rows(inv, full[keep]), data)


def test_plain_reference_stripes_one_mb_rows():
    mib = rs_plain.BLOCK
    dat = bytes([7]) * (10 * mib + 3)  # one full row and three bytes
    assert rs_plain.shard_size_of(len(dat)) == 2 * mib
    shards = rs_plain.stripe(dat, 0, 2)
    assert shards.shape == (10, 2 * mib)
    assert shards[0, :mib].all() and shards[9, :mib].all()
    assert list(shards[0, mib:mib + 4]) == [7, 7, 7, 0]
    assert not shards[1, mib:].any()


# ------------------------------------------------------------- the trace


def _event(name, start_us, dur_us):
    return SimpleNamespace(name=name, start_ns=start_us * 1e3,
                           duration_ns=dur_us * 1e3)


def _profile():
    modules = SimpleNamespace(name="XLA Modules", events=[
        _event("jit_reconstruct(11)", 0, 100),
        _event("jit_reconstruct(22)", 300, 100),
        _event("jit_encode(33)", 1000, 500),
    ])
    ops = SimpleNamespace(name="XLA Ops", events=[
        _event("fusion.1", 0, 60), _event("copy.2", 50, 50),  # overlap
        _event("fusion.1", 300, 100), _event("custom-call.3", 1000, 500),
    ])
    steps = SimpleNamespace(name="Steps", events=[_event("0", 0, 5000)])
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(
            name="python", events=[_event("$run", 0, 9000)])]),
        SimpleNamespace(name="/device:TPU:0", lines=[modules, ops, steps]),
    ])


def test_trace_reduction_on_a_synthetic_trace():
    tr = trace.read(_profile())
    assert tr.chips == 1
    assert trace.busy_seconds(tr) == pytest.approx(700e-6)
    top = trace.top_programs(tr)
    assert top[0][0] == "jit_encode" and top[0][1] == pytest.approx(500e-6)
    assert top[1][0] == "jit_reconstruct"
    gaps = trace.idle_gaps(tr)
    assert gaps[0][0] == "before jit_encode"
    assert gaps[0][1] == pytest.approx(600e-6)
    assert gaps[1] == ["before jit_reconstruct", pytest.approx(200e-6)]
    # a trace with no device plane gives nothing, not zero
    host_only = SimpleNamespace(planes=_profile().planes[:1])
    assert trace.busy_seconds(trace.read(host_only)) is None


def test_roofline_and_idle_reducers_read_the_trace():
    tr = trace.read(_profile())
    chip = peaks.lookup("TPU v5 lite")
    from benchmark.reducers import device_idle, roofline
    params = {"programs": ["reconstruct"], "work": "reconstruct_bytes",
              "work_args": ["reconstruct_lost_bytes"]}
    lost = 819 * 200 * 1000 // 11  # 11x that takes 200 us at the roofline
    got = roofline.reduce(tr, {"reconstruct_lost_bytes": lost}, params, chip)
    assert got == pytest.approx(100.0, rel=1e-3)
    assert roofline.reduce(tr, {}, params, chip) is None
    assert roofline.reduce(tr, {"reconstruct_lost_bytes": lost},
                           {**params, "programs": ["absent"]}, chip) is None
    # two verbs behind one program name: dealt out in the order they ran
    facts = {"verb_batches": [["encode", 2], ["rebuild", 1]],
             "encode_dat_bytes": 819 * 100 * 1000 * 10 // 14,
             "rebuild_shard_bytes": 819 * 100 * 1000 // 12,
             "rebuild_lost_shards": 2}
    enc = {"programs": ["jit_"], "verb": "encode", "work": "encode_bytes",
           "work_args": ["encode_dat_bytes"]}
    reb = {"programs": ["jit_"], "verb": "rebuild", "work": "rebuild_bytes",
           "work_args": ["rebuild_shard_bytes", "rebuild_lost_shards"]}
    # 100 us of work over the two reconstruct events (200 us) / the encode
    # event (500 us) of the synthetic trace
    assert roofline.reduce(tr, facts, enc, chip) == pytest.approx(50, rel=1e-3)
    assert roofline.reduce(tr, facts, reb, chip) == pytest.approx(20, rel=1e-3)
    # the server counted another number of batches than the trace holds
    facts["verb_batches"][1][1] = 2
    assert roofline.reduce(tr, facts, enc, chip) is None
    idle = device_idle.reduce(tr, {"window_s": 1400e-6}, {}, chip)
    assert idle == pytest.approx(50.0)
    assert device_idle.reduce(tr, {}, {}, chip) is None


# ------------------------------------------- counters and small pieces


METRICS_A = """# HELP x
SeaweedFS_volumeServer_ec_batch_size_sum 10
SeaweedFS_volumeServer_ec_batch_size_count 5
SeaweedFS_volumeServer_ec_read_route_total{route="batched"} 7
SeaweedFS_volumeServer_ec_read_route_total{route="native"} 1
SeaweedFS_volumeServer_device_dispatches_total{device="0",workload="bulk"} 3
SeaweedFS_volumeServer_device_dispatches_total{device="host",workload="bulk"} 9
"""
METRICS_B = METRICS_A.replace("_sum 10", "_sum 40").replace(
    "_count 5", "_count 15").replace('native"} 1', 'native"} 3')


def test_counter_ratio_reader_takes_the_windows_delta():
    before, after = parse_metrics(METRICS_A), parse_metrics(METRICS_B)
    assert series_sum(after, "device_dispatches_total", {"workload": "bulk"},
                      {"device": "host"}) == 3
    spec = {"num": [{"series": "ec_batch_size_sum"}],
            "den": [{"series": "ec_batch_size_count"}]}
    assert metrics_eval.ratio(spec, before, after, {}) == pytest.approx(3.0)
    spec = {"num": [{"series": "ec_read_route_total",
                     "labels": {"route": "native"}}],
            "den": [{"fact": "gets"}], "scale": 100}
    assert metrics_eval.ratio(spec, before, after, {"gets": 8}) == 25.0
    # nothing to read: a missing fact or an empty denominator
    assert metrics_eval.ratio(spec, before, after, {}) is None
    assert metrics_eval.ratio(spec, before, after, {"gets": 0}) is None


def test_read_index_and_pool(tmp_path):
    entries = [(1, 8, 4096), (2, 8 + 4136, 16384), (3, 40000, -1),
               (4, 8 + 4136 + 16424, 100)]
    raw = b"".join(
        k.to_bytes(8, "big") + (off // 8).to_bytes(4, "big")
        + size.to_bytes(4, "big", signed=True) for k, off, size in entries)
    p = tmp_path / "1.ecx"
    p.write_bytes(raw)
    index = read_index(str(p))
    assert index == {1: (8, 4136), 2: (4144, 16424), 4: (20568, 128)}
    sizes = [4096, 16384] * 50
    lost = {k: (k % 5 == 0) * 100 for k in range(1, 101)}
    pool = pick_pool(sizes, lost, 8, 0.75, seed=3)
    assert len(pool) == len(set(pool)) == 16
    assert sum(lost[k] > 0 for k in pool) == 12  # 3 in 4 of each size
    assert pick_pool(sizes, lost, 8, 0.75, seed=3) == pool
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
    assert percentile(list(range(1, 101)), 0.99) == 99


# ----------------------------------------------- found by name, no edits


def test_a_file_added_beside_the_others_is_found(tmp_path):
    """A later PR adds a cell, a configuration, a mix and a counter-backed
    per-layer metric as files and entries: run.py and the readers find
    them by name."""
    tree = tmp_path / "tree"
    shutil.copytree(os.path.join(REPO, "benchmark"), tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "seaweedfs_tpu"), tree / "seaweedfs_tpu")
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(tree / "benchmark/configs/ec-bulk-1g.json"))
    cfg["name"] = "ec-bulk-one-lost"
    cfg["lost_shards"] = [12]
    cfg["rehearse"]["volumes"] = 1
    json.dump(cfg, open(tree / "benchmark/configs/ec-bulk-one-lost.json", "w"))
    mix = json.load(open(tree / "benchmark/traffic/encode-rebuild.json"))
    mix["check_params"]["readback_per_volume"] = 5
    json.dump(mix, open(tree / "benchmark/traffic/encode-rebuild-5.json", "w"))
    json.dump({"ratio": {"num": [{"series": "ec_bulk_batches_total",
                                  "labels": {"pipeline": "rebuild"}}],
                         "den": [{"fact": "rebuild_lost_shards"}]}},
              open(tree / "benchmark/layer_metrics/rebuild_batches.json", "w"))
    cell = "ec-bulk-one-lost.encode-rebuild-5"
    bench["configs"].append({
        **bench["configs"][1], "name": "ec-bulk-one-lost",
        "file": "benchmark/configs/ec-bulk-one-lost.json"})
    bench["workloads"].append({
        "name": cell, "config": "ec-bulk-one-lost",
        "traffic": "encode-rebuild-5", "chips": 1, "why": "one parity lost"})
    for m in bench["end_to_end"]:
        if BULK_CELL in m.get("workloads", []):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "rebuild_batches", "unit": "batches", "better": "lower",
        "source": "program_counter", "layer": "bulk pipeline",
        "moves": "ec_rebuild_rate", "workloads": [cell]})
    json.dump(bench, open(tree / "BENCHMARK.json", "w"))
    line = rehearse(cell, "--trace", "1", cwd=str(tree))
    assert line["correct"] is True and line["attempted"] == 2
    # a 12 MiB volume has 2 MiB shards: one rebuild batch
    assert line["metrics"]["rebuild_batches"] == {
        "value": 1.0, "unit": "batches"}
    assert line["compared"]["rebuild_files_differing"] == {
        "value": 0, "limit": 0}


# ------------------------------------------------------------ rehearsals


def _two_cores():
    # the servers compile on every core they may use; the other xdist
    # workers run tests with millisecond margins beside them
    cores = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, cores[-2:])


def run_cell(cell, *argv, cwd=REPO, timeout=900):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 12345), "--seconds", "3",
         *argv],
        cwd=cwd, env=env, timeout=timeout, capture_output=True, text=True,
        preexec_fn=_two_cores,
    )


def rehearse(cell, *argv, cwd=REPO):
    r = run_cell(cell, "--rehearse", *argv, cwd=cwd)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    # the contract's keys, `compared` last, and the numbers compared as
    # the last lines of standard error
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    tail = r.stderr.strip().splitlines()[-len(line["compared"]):]
    for row, (name, c) in zip(tail, line["compared"].items()):
        assert row == f"compared {name}: value={c['value']} limit={c['limit']}"
    assert line["correct"] == all(
        c["value"] <= c["limit"] for c in line["compared"].values())
    # off the chip a time, a rate or a share of the device is never a
    # number: only what the program counted is
    traced = argv[argv.index("--trace") + 1] == "1"
    group = "per_layer" if traced else "end_to_end"
    # the tree's own declaration: a copy's cell is declared in the copy
    named = {m["name"]: m for m in declarations.load(cwd)[group]
             if cell in m.get("workloads", [cell])}
    if cell in (GET_CELL, BULK_CELL):
        assert set(line["metrics"]) >= {
            n for n, m in named.items() if m["source"] != "program_counter"}
        if not traced:
            assert set(line["metrics"]) == set(named)
    for name, m in line["metrics"].items():
        if name in named and named[name]["source"] != "program_counter":
            assert m["value"] is None, name
        assert UNIT.match(m["unit"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    return line


def test_get_cell_rehearsed_with_trace():
    line = rehearse(GET_CELL, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    # every client completed a GET: how many more, a loaded host decides
    assert line["attempted"] >= 16
    assert line["compared"] == {
        "failed_gets": {"value": 0, "limit": 0},
        "wrong_bodies": {"value": 0, "limit": 0}}
    counted = {n for n, m in line["metrics"].items() if m["value"] is not None}
    assert counted == {"batch_size_mean", "device_calls_per_get",
                       "host_route_pct"}
    assert line["metrics"]["batch_size_mean"]["value"] >= 1


def test_get_cell_control_and_fault_come_out_incorrect():
    # the control: a stale survivor behind the reconstruct
    line = rehearse(GET_CELL, "--trace", "0", "--control", "stale_shard")
    assert line["correct"] is False and line["failed"] > 0
    assert (line["compared"]["failed_gets"]["value"]
            + line["compared"]["wrong_bodies"]["value"]) == line["failed"]
    # an answer altered where it is produced
    line = rehearse(GET_CELL, "--trace", "0", "--fault", "get_flip_byte")
    assert line["correct"] is False
    assert line["compared"]["wrong_bodies"]["value"] > 0


def test_bulk_cell_rehearsed_with_trace():
    line = rehearse(BULK_CELL, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    assert line["compared"] == {
        "encode_files_differing": {"value": 0, "limit": 0},
        "rebuild_files_differing": {"value": 0, "limit": 0},
        "readback_wrong_bodies": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("how, number", [
    (("--control", "stale_shard"), "rebuild_files_differing"),
    (("--fault", "bulk_flip_byte"),
     "encode_files_differing+rebuild_files_differing"),
    (("--fault", "bulk_drop_half"), "encode_files_differing"),
])
def test_bulk_cell_control_and_faults_come_out_incorrect(how, number):
    line = rehearse(BULK_CELL, "--trace", "0", *how)
    assert line["correct"] is False
    assert sum(line["compared"][n]["value"] for n in number.split("+")) > 0


def test_without_a_chip_the_run_fails_and_prints_no_result():
    r = run_cell(GET_CELL, "--trace", "0", timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "needs the chip" in r.stderr
    assert '"correct"' not in r.stdout


def test_outside_a_checkout_the_run_fails(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    r = run_cell(GET_CELL, "--trace", "0", cwd=str(tmp_path), timeout=120)
    assert r.returncode != 0 and '"correct"' not in r.stdout
