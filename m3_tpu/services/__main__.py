"""Service CLI: `python -m m3_tpu.services <service> -f config.yml`
(reference: src/cmd/services/*/main/main.go — one '-f' flag per binary)."""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def _print_backend():
    """Say once, at start, which backend this process computes on. A
    chip belongs to one process: on a one-chip host exactly one service
    (the dbnode with its embedded coordinator) may see the TPU here; the
    others are started with JAX_PLATFORMS=cpu."""
    import jax

    # Imported HERE, before the listeners open: the write path's first
    # shard-routing hash_batch otherwise pays this module's ~1 s import
    # (jax.experimental.pallas) inside the first acknowledged write.
    from ..ops import pallas_codec
    from ..utils import compile_cache

    cache_dir = compile_cache.configure()
    devs = jax.devices()
    print(f"m3_tpu backend: platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind} count={len(devs)} "
          f"codec={'pallas' if pallas_codec.enabled() else 'xla'} "
          f"compile_cache={cache_dir}", flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(prog="m3_tpu.services")
    parser.add_argument("service",
                        choices=["dbnode", "coordinator", "aggregator",
                                 "collector", "kv"])
    parser.add_argument("-f", "--config", required=False, default=None,
                        help="yaml config file (defaults apply if omitted)")
    args = parser.parse_args(argv)

    from . import config as cfgmod
    from . import run as runmod

    if args.config:
        cfg = cfgmod.load_file(args.config, args.service)
    else:
        cfg = cfgmod.load_dict({}, args.service)

    if args.service != "kv":  # the KV service runs no device code
        _print_backend()

    if args.service == "dbnode":
        handle = runmod.run_dbnode(cfg)
        print(f"m3_tpu dbnode listening on {handle.endpoint}", flush=True)
        if handle.coordinator is not None:
            print(f"embedded coordinator on {handle.coordinator.endpoint}",
                  flush=True)
    elif args.service == "aggregator":
        handle = runmod.run_aggregator(
            cfg,
            on_placement=lambda shards: print(
                f"placement update: owned={shards}", flush=True))
        print(f"m3_tpu aggregator listening on {handle.endpoint}", flush=True)
        if handle.admin is not None:
            print(f"m3_tpu aggregator admin on {handle.admin_endpoint}",
                  flush=True)
    elif args.service == "kv":
        handle = runmod.run_kv(cfg)
        print(f"m3_tpu kv listening on {handle.endpoint}", flush=True)
    elif args.service == "coordinator":
        if not cfg.kv_endpoint:
            print("standalone coordinator requires kv_endpoint (or use "
                  "dbnode with a coordinator section for the single-binary "
                  "quickstart)", file=sys.stderr)
            return 2
        handle = runmod.run_coordinator_standalone(cfg)
        print(f"m3_tpu coordinator listening on {handle.endpoint}", flush=True)
        carbon = getattr(handle, "carbon", None)
        if carbon is not None:
            print(f"m3_tpu carbon listening on {carbon.endpoint}", flush=True)
    else:
        print("collector runs embedded; see m3_tpu.services.run.run_collector",
              file=sys.stderr)
        return 2

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    handle.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
