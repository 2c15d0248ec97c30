"""chip_smoke.py rehearsed on the CPU, and refused without a chip.

The smoke's real run is on the TPU through the chip tool.  Here its
phases run at a tiny size on the CPU backend (an option only this test
passes), so that a broken path, argument or counter check is found at no
chip time; and the script as the driver runs it — no option — must exit
non-zero on this chip-less box before any phase.
"""
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _two_cores():
    # the smoke's servers compile on every core they may use; the other
    # xdist workers run tests with millisecond margins beside them
    cores = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, cores[-2:])


def run_smoke(*argv, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # the children are plain one-device servers, not the suite's 8-way mesh
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, SMOKE, *argv], cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True, preexec_fn=_two_cores,
    )


def test_smoke_phases_rehearsed_on_cpu():
    r = run_smoke("--rehearse-mib", "24", "--reads", "60", timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the last line is the contract's object and nothing more
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    out = "\n".join(lines[:-1])
    for phase in (
        "load", "encode", "encode_verify", "pin_warm", "degraded_read",
        "rebuild", "scrub", "restart_pin_warm",
    ):
        assert f"phase {phase}: " in out, phase
    assert "byte-equal to the host codec" in out
    assert "all byte-equal to what was written" in out
    assert "shards [3, 11] byte-equal to the originals" in out
    assert "CORRUPT: [0, 0, 1, 0] mismatch bytes backend=device_resident" in out
    # the restarted process compiled nothing: by JAX's own events every
    # compile request was a persistent-cache hit
    m = re.search(
        r"warm plan \(restarted process\): (\d+) shapes, .*persistent "
        r"compile cache: (\d+) requests, (\d+) hits, (\d+) misses", out)
    assert m, out[-2000:]
    plan, requests, hits, misses = map(int, m.groups())
    assert requests == hits >= plan > 0 and misses == 0


def test_smoke_without_a_chip_fails_before_any_phase():
    r = run_smoke(timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "needs the chip" in r.stderr
    assert "phase " not in r.stdout
    assert '"ok"' not in r.stdout
