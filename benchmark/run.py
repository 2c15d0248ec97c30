#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (benchmark/configs/<config>.json: a deployment
of one master and one volume server, its flags, its volumes and how they
are brought to the state the traffic needs) under a traffic mix
(benchmark/traffic/<traffic>.json: a generator of benchmark/generators/
and its parameters).  The run starts the cluster as child processes,
builds the data from --seed through the front door, sets up, warms up,
measures one window, checks what the window produced against the plain
reference, prints the contract's JSON object as the last line of
standard output, stops every child and removes its data.

This process never imports JAX while a child holds the chip: the volume
server is the only process that touches it, and every device number comes
from that process's own status, counters and profiler trace.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import metrics_eval, trace as trace_mod  # noqa: E402
from benchmark.cluster import (  # noqa: E402
    BenchFailure, Cluster, check, check_no_failures, check_on_chip,
    compile_cache_counts, device_status, say, scrape, series_sum, wait_http,
)
from benchmark.harness import Context  # noqa: E402

# a traced window has to end inside one capture of /debug/profile, which
# the server caps at 30 s; the capture starts this long before the window
PROFILE_CAP_S = 30.0
PROFILE_LEAD_S = 1.5
PROFILE_TAIL_S = 3.5


def load_json(*parts: str) -> dict:
    with open(os.path.join(REPO, *parts), encoding="utf-8") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                     f"(has {[c['name'] for c in bench['workloads']]})")


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def with_overrides(flags: list[str], overrides: list[str]) -> list[str]:
    """`flags` with every "-key=value" of `overrides` put in place of the
    flag of the same key."""
    keys = {o.split("=", 1)[0] for o in overrides}
    return [f for f in flags if f.split("=", 1)[0] not in keys] + overrides


class Run:
    def __init__(self, args):
        self.args = args
        self.bench = load_json("BENCHMARK.json")
        self.cell = find_cell(self.bench, args.workload)
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.cell["config"])
        self.config = load_json(cfg_entry["file"])
        self.mix = load_json(
            "benchmark", "traffic", self.cell["traffic"] + ".json")
        self.rehearse = args.rehearse
        self.traced = bool(args.trace)

    # ------------------------------------------------------------ set-up

    def make_cluster(self, work: str) -> Cluster:
        volume_flags = list(self.config["volume_flags"])
        env = {}
        if self.rehearse:
            volume_flags = with_overrides(
                volume_flags, self.config["rehearse"]["volume_flags"])
            env["JAX_PLATFORMS"] = "cpu"
        if self.traced:
            env["SWFS_DEBUG"] = "1"
        launcher = None
        if self.args.fault:
            env["PYTHONPATH"] = REPO
            launcher = [sys.executable,
                        os.path.join(HERE, "faults", "launch.py"),
                        self.args.fault]
        cluster = Cluster(work, list(self.config["master_flags"]),
                          volume_flags, env, launcher)
        if self.rehearse:
            # plain one-device servers, whatever mesh the caller forced
            cluster.env.pop("XLA_FLAGS", None)
        return cluster

    def check_device(self, ctx, dev: dict) -> None:
        check_on_chip(dev, ctx.enforce, self.cell["chips"])
        check(not (self.rehearse and dev["platform"] == "tpu"),
              "--rehearse is for a machine without the chip: here JAX "
              "found a TPU, run the cell at its own size")

    async def set_up(self, ctx) -> dict:
        dev = await device_status(ctx.session, ctx.cluster)
        if dev.get("initialised"):
            # a server with a device cache knows its device at once: a
            # missing chip ends the run before any data is loaded
            self.check_device(ctx, dev)
        steps = [dict(s) for s in self.config["setup"]]
        if ctx.control:
            at = next((i for i, s in enumerate(steps)
                       if s["step"] == "lose_shards"), None)
            if at is not None:
                steps.insert(at, {"step": ctx.control})
        for spec in steps:
            name = spec.pop("step")
            t0 = time.monotonic()
            module = importlib.import_module(f"benchmark.steps.{name}")
            await module.run(ctx, **spec)
            ctx.cluster.assert_alive()
            say(f"set-up step {name}: {time.monotonic() - t0:.1f} s")
        dev = await device_status(ctx.session, ctx.cluster)
        self.check_device(ctx, dev)
        check_no_failures(dev)
        return dev

    # ------------------------------------------------------------ tracing

    async def capture(self, ctx, seconds: float) -> dict:
        url = (f"http://{ctx.cluster.volume_http}/debug/profile"
               f"?seconds={seconds}")
        async with ctx.session.get(url) as r:
            check(r.status == 200, f"/debug/profile: HTTP {r.status} "
                  f"{await r.text()}")
            return await r.json()

    # -------------------------------------------------------------- a run

    async def run(self) -> dict:
        import aiohttp

        from seaweedfs_tpu.shell import CommandEnv

        args = self.args
        work = tempfile.mkdtemp(prefix="swfs_bench_")
        cluster = self.make_cluster(work)
        ctx = Context(
            config=self.config,
            sizes=self.config["rehearse"] if self.rehearse else self.config,
            seed=args.seed, enforce=not self.rehearse,
            control=args.control, cluster=cluster,
        )
        try:
            timeout = aiohttp.ClientTimeout(total=600)
            conn = aiohttp.TCPConnector(limit=64)
            async with aiohttp.ClientSession(
                    timeout=timeout, connector=conn) as session:
                ctx.session = session
                cluster.start_master()
                await wait_http(
                    session, f"http://{cluster.master_http}/cluster/status",
                    cluster, 60)
                cluster.start_volume()
                await wait_http(
                    session, f"http://{cluster.volume_http}/status",
                    cluster, 180)
                say(f"cluster answers: {time.monotonic() - T_START:.1f} s "
                    "from process start")
                ctx.env = CommandEnv([cluster.master], out=io.StringIO())
                await ctx.env.acquire_lock()
                return await self.measure(ctx)
        except BaseException:
            sys.stderr.write("---- volume server log tail ----\n"
                             + cluster.log_tail("volume") + "\n")
            raise
        finally:
            cluster.stop_all()
            shutil.rmtree(work, ignore_errors=True)

    async def measure(self, ctx) -> dict:
        args, session, cluster = self.args, ctx.session, ctx.cluster
        await self.set_up(ctx)
        gen_module = importlib.import_module(
            f"benchmark.generators.{self.mix['generator']}")
        generator = gen_module.Generator(ctx, self.mix["params"])
        await generator.prepare()
        budget = profile = None
        if self.traced:
            # the profiler's one-time start-up is set-up's, not the window's
            await self.capture(ctx, 0.2)
            budget = min(float(args.seconds),
                         self.mix.get("trace_seconds", PROFILE_CAP_S),
                         PROFILE_CAP_S - PROFILE_LEAD_S - PROFILE_TAIL_S)
            profile = asyncio.ensure_future(self.capture(
                ctx, budget + PROFILE_LEAD_S + PROFILE_TAIL_S))
            await asyncio.sleep(PROFILE_LEAD_S)
        dev_before = await device_status(session, cluster)
        before = await scrape(session, cluster)
        setup_s = time.monotonic() - T_START
        say(f"set-up: {setup_s:.1f} s from process start; "
            f"{compile_cache_counts(dev_before)}")

        result = await generator.window(float(args.seconds), budget)

        after = await scrape(session, cluster)
        dev = await device_status(session, cluster)
        cluster.assert_alive()
        check_no_failures(dev)
        compiles = (dev["compile_cache"]["requests"]
                    - dev_before["compile_cache"]["requests"])
        inline = (series_sum(after, "ec_device_compile_total",
                             {"result": "miss"})
                  - series_sum(before, "ec_device_compile_total",
                               {"result": "miss"}))
        say(f"compiles inside the window: {compiles} requests to the "
            f"persistent cache, {int(inline)} inline reconstruct shapes")
        check(not ctx.enforce or (compiles == 0 and inline == 0),
              "something compiled inside the measured window")
        memory_peak = metrics_eval.device_bytes_held(dev, before, after)
        captured = await profile if profile is not None else None

        compared = dict(result["compared"])
        if self.mix.get("check"):
            checker = importlib.import_module(
                f"benchmark.checks.{self.mix['check']}")
            compared.update(await checker.run(
                ctx, result, **self.mix.get("check_params", {})))
        # the device is freed before the trace is read: the reader imports
        # JAX (held to the CPU), and no second process may want the chip
        cluster.stop_all()

        facts = {**result["facts"], "window_s": result["window_s"]}
        device = {
            "platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["device_count"], "memory_peak_bytes": memory_peak,
        }
        out = {
            "correct": all(v <= limit for v, limit in compared.values()),
            "attempted": result["attempted"],
            "failed": result["failed"],
            "workload": self.cell["name"],
            "seed": args.seed,
        }
        if self.rehearse:
            out["rehearsal"] = True
        if not self.traced:
            values = {name: result["values"][key]
                      for name, key in self.mix["reports"].items()}
            values["setup_s"] = setup_s
            out["metrics"] = self.render(
                metrics_of(self.bench, "end_to_end", self.cell["name"]),
                values)
        else:
            tr = await asyncio.to_thread(
                trace_mod.load, captured["trace_dir"],
                args.dump_trace and args.dump_trace + ".planes")
            busy = trace_mod.busy_seconds(tr) if ctx.enforce else None
            check(not ctx.enforce or busy,
                  "the traced window shows no operation on the device")
            specs = metrics_of(self.bench, "per_layer", self.cell["name"])
            out["metrics"] = self.render(specs, metrics_eval.layer_values(
                specs, before, after, facts, tr if ctx.enforce else None,
                dev["device_kind"]))
            if args.dump_trace:
                trace_mod.dump(tr, args.dump_trace)
            if busy:
                device["busy_s"] = busy
                device["window_s"] = result["window_s"]
                out["breakdown"] = {
                    "device_ops": trace_mod.top_programs(tr),
                    "idle_gaps": trace_mod.idle_gaps(tr),
                }
        out["device"] = device
        out["compared"] = {
            name: {"value": v, "limit": limit}
            for name, (v, limit) in compared.items()
        }
        return out

    def render(self, specs: list[dict], values: dict) -> dict:
        """{name: {"value", "unit"}} for the metrics that have a value.
        Off the chip (a rehearsal) only counts are numbers: a time, a
        rate or a share of the device read on a CPU is never written
        under a device metric's name."""
        out = {}
        for spec in specs:
            value = values.get(spec["name"])
            if self.rehearse and spec["source"] != "program_counter":
                out[spec["name"]] = {"value": None, "unit": spec["unit"]}
            elif value is not None:
                out[spec["name"]] = {"value": value, "unit": spec["unit"]}
        return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # CPU rehearsal at the sizes under "rehearse" in the configuration's
    # file: device identity reported and not enforced, no device number
    ap.add_argument("--rehearse", action="store_true")
    # the runs that have to come out `correct: false` (benchmark/README.md)
    # (--control names a step of benchmark/steps/: stale_shard)
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="", help=argparse.SUPPRESS)
    ap.add_argument("--dump-trace", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, "seaweedfs_tpu")):
        sys.stderr.write(
            "benchmark/run.py: no seaweedfs_tpu/ beside benchmark/: run it "
            "from a checkout of the repository\n")
        return 2

    def on_term(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, on_term)
    try:
        out = asyncio.run(Run(args).run())
    except BenchFailure as e:
        sys.stderr.write(f"benchmark/run.py: FAILED: {e}\n")
        return 1
    for name, c in out["compared"].items():
        sys.stderr.write(
            f"compared {name}: value={c['value']} limit={c['limit']}\n")
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
