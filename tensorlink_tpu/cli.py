"""CLI node runner (reference bin/run_node.py:213-289 + run-node.sh).

``python -m tensorlink_tpu.cli --config config.json`` (or ``run-node``
console script) starts a worker / validator / user node from an operator
config file, prints the terminal status dashboard on an interval (reference
print_ui_status, p2p/torch_node.py:963-1049), and shuts down cleanly on
SIGINT/SIGTERM. No mining-subprocess management — that is GPU-market
machinery with no TPU analogue (SURVEY §7.4)."""

from __future__ import annotations

import argparse
import json
import signal
import sys
import time

from tensorlink_tpu.core.config import NodeConfig, load_config


def status_report(node) -> str:
    """One-screen text dashboard (reference print_ui_status)."""
    st = node.status()
    lines = [
        f"=== tensorlink_tpu {st['role']} {st['id'][:16]} ===",
        f"addr {st['addr'][0]}:{st['addr'][1]}  uptime {st['uptime_s']:.0f}s  "
        f"dht_keys {st['dht_keys']}",
        f"peers ({len(st['peers'])}):",
    ]
    for nid, p in sorted(st["peers"].items()):
        lat = p.get("latency_s")
        lines.append(
            f"  {nid} {p.get('role', '?'):<10} "
            f"tx {p.get('sent', 0):>10}  rx {p.get('recv', 0):>10}  "
            f"lat {f'{lat * 1e3:.1f}ms' if lat else '—':>8}  "
            f"ghosts {p.get('ghosts', 0)}"
        )
    return "\n".join(lines)


def make_node(cfg: NodeConfig):
    from tensorlink_tpu.nodes.runners import UserNode, ValidatorNode, WorkerNode

    cls = {"worker": WorkerNode, "validator": ValidatorNode, "user": UserNode}[
        cfg.role
    ]
    return cls(cfg)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="run-node", description=__doc__)
    ap.add_argument("--config", "-c", default="config.json",
                    help="operator config file (reference bin/config.json)")
    ap.add_argument("--role", choices=["worker", "validator", "user"],
                    help="override the config's role")
    def seed_addr(s: str) -> tuple[str, int]:
        host, sep, port = s.rpartition(":")
        if not sep or not host or not port.isdigit():
            raise argparse.ArgumentTypeError(
                f"expected HOST:PORT, got {s!r}"
            )
        return (host, int(port))

    ap.add_argument("--seed", action="append", default=[], type=seed_addr,
                    metavar="HOST:PORT", help="seed validator (repeatable)")
    ap.add_argument("--port", type=int, help="listen port override")
    ap.add_argument("--local", action="store_true",
                    help="local test mode (127.0.0.1, no UPnP)")
    ap.add_argument("--ui-interval", type=float, default=180.0,
                    help="status dashboard interval, seconds (0 = off)")
    args = ap.parse_args(argv)

    try:
        cfg = load_config(args.config)
    except FileNotFoundError:
        cfg = NodeConfig()
    if args.role:
        from tensorlink_tpu.core.config import ROLE_CONFIGS, _coerce

        # _coerce drops fields the target role's config doesn't define
        # (e.g. worker 'mining' when switching to validator)
        flat = {k: v for k, v in cfg.__dict__.items() if k != "role"}
        cfg = _coerce(ROLE_CONFIGS[args.role], flat)
    if args.seed:
        cfg.seed_validators = list(args.seed)
    if args.port is not None:
        cfg.port = args.port
    if args.local:
        cfg.local_test = True

    from tensorlink_tpu.core.devices import configure_compile_cache

    configure_compile_cache()  # every role's compiles share the one cache
    try:
        node = make_node(cfg).start()
    except Exception as e:
        # a node that cannot start (no accelerator backend, port in use,
        # bad keys) is a failed launch, not a degraded one
        print(f"run-node: {cfg.role} failed to start: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"id": node.node_id, "role": node.role, "port": node.port}))

    stop = {"flag": False}

    def handle(sig, frame):
        stop["flag"] = True

    signal.signal(signal.SIGINT, handle)
    signal.signal(signal.SIGTERM, handle)

    last_ui = time.monotonic()
    try:
        while not stop["flag"]:
            time.sleep(0.5)
            if args.ui_interval and time.monotonic() - last_ui >= args.ui_interval:
                print(status_report(node), flush=True)
                last_ui = time.monotonic()
    finally:
        node.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
