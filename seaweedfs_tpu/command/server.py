"""`server` — master + volume (+ filer, + s3) in one process
(reference: weed/command/server.go)."""
from __future__ import annotations

import asyncio

from . import common_args
from ..utils import config as config_util

NAME = "server"
HELP = "start master + volume server (+ -filer, + -s3) in one process"


def add_args(p) -> None:
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-master.port", dest="master_port", type=int, default=9333)
    p.add_argument("-volume.port", dest="volume_port", type=int, default=8080)
    p.add_argument("-dir", default=".", help="volume data directories (comma-separated)")
    p.add_argument("-volume.max", dest="volume_max", default="8")
    p.add_argument(
        "-volumeSizeLimitMB", dest="volume_size_limit_mb", type=int, default=30 * 1024
    )
    p.add_argument("-defaultReplication", dest="default_replication", default="000")
    p.add_argument(
        "-ec.backend", dest="ec_backend", default="auto",
        choices=["auto", "cpu", "native", "numpy", "xla", "pallas"],
    )
    p.add_argument(
        "-ec.deviceCacheMB", dest="ec_device_cache_mb", type=int, default=0,
        help="pin mounted EC shards in device HBM up to this budget "
        "(degraded reads serve from the fused reconstruct kernels)",
    )
    p.add_argument("-filer", action="store_true", help="also run a filer")
    p.add_argument("-filer.port", dest="filer_port", type=int, default=8888)
    p.add_argument("-filer.db", dest="filer_db", default="")
    p.add_argument("-s3", action="store_true", help="also run the S3 gateway")
    p.add_argument("-s3.port", dest="s3_port", type=int, default=8333)
    p.add_argument("-s3.config", dest="s3_config", default="")
    common_args.add_metrics_args(p)
    common_args.add_obs_args(p)
    # the co-hosted master carries the incident plane's engine/bundler
    common_args.add_slo_incident_args(p)


async def run(args) -> None:
    common_args.apply_obs_args(args)
    from ..server.master import MasterServer
    from ..server.volume import VolumeServer

    from ..security import guard as guard_mod
    from ..storage import types as storage_types

    if args.volume_size_limit_mb * 1024 * 1024 > storage_types.MAX_POSSIBLE_VOLUME_SIZE:
        storage_types.set_offset_size(5)  # see command/master.py

    jwt_key = config_util.jwt_signing_key()
    white_list = guard_mod.from_security_toml()
    # every co-hosted role pushes the shared process registry under its
    # own job name, as the reference's combined `weed server` does with
    # its shared Gather — consumers aggregate with a job filter
    metrics_kw = common_args.metrics_kwargs(args)
    ms = MasterServer(
        ip=args.ip,
        port=args.master_port,
        volume_size_limit_mb=args.volume_size_limit_mb,
        default_replication=args.default_replication,
        jwt_signing_key=jwt_key,
        jwt_expires_sec=config_util.jwt_expires_sec(),
        white_list=white_list,
        **metrics_kw,
        **common_args.slo_incident_kwargs(args),
    )
    await ms.start()

    dirs = [d.strip() for d in args.dir.split(",") if d.strip()]
    counts = [int(c) for c in str(args.volume_max).split(",")]
    if len(counts) == 1:
        counts = counts * len(dirs)
    if args.ec_device_cache_mb > 0:
        from ..ops.rs_resident import enable_persistent_compile_cache

        enable_persistent_compile_cache()
    vs = VolumeServer(
        masters=[ms.advertise_url],
        directories=dirs,
        ip=args.ip,
        port=args.volume_port,
        max_volume_counts=counts,
        ec_backend=args.ec_backend,
        ec_device_cache_mb=args.ec_device_cache_mb,
        jwt_signing_key=jwt_key,
        white_list=white_list,
        **metrics_kw,
    )
    await vs.start()

    if args.filer or args.s3:
        import argparse

        from . import filer as filer_cmd

        # take every default from the filer command's own parser so new
        # filer flags can never drift out of sync with `server`
        fparser = argparse.ArgumentParser()
        filer_cmd.add_args(fparser)
        fargs = fparser.parse_args([])
        fargs.masters = ms.advertise_url
        fargs.db_path = args.filer_db
        fargs.ip = args.ip
        fargs.port = args.filer_port
        fargs.metrics_address = args.metrics_address
        fargs.metrics_interval_seconds = args.metrics_interval_seconds
        fs = filer_cmd.build_filer_server(fargs)
        await fs.start()
        if args.s3:
            from . import s3 as s3_cmd

            # same derive-from-parser discipline as the filer block above
            sparser = argparse.ArgumentParser()
            s3_cmd.add_args(sparser)
            sargs = sparser.parse_args([])
            sargs.filer = f"{args.ip}:{fs.port}"
            sargs.filer_grpc = f"{fs.ip}:{fs.grpc_port}"
            sargs.ip = args.ip
            sargs.port = args.s3_port
            sargs.s3_config = args.s3_config
            sargs.metrics_address = args.metrics_address
            sargs.metrics_interval_seconds = args.metrics_interval_seconds
            s3 = s3_cmd.build_s3_server(sargs)
            await s3.start()

    await asyncio.Event().wait()
