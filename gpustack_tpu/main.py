"""CLI entrypoint (reference gpustack/main.py + cmd/start.py).

``python -m gpustack_tpu start`` runs a server (with embedded worker), a
pure worker when ``--server-url`` is given — same role derivation as the
reference (cmd/start.py:727-730).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import sys

from gpustack_tpu.observability.tracing import LOG_FORMAT


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "gpustack-tpu", description="TPU-native model serving cluster manager"
    )
    sub = p.add_subparsers(dest="command")

    start = sub.add_parser("start", help="start server or worker")
    start.add_argument("--config-file", default="")
    start.add_argument("--server-url", default=None,
                       help="run as worker against this server")
    start.add_argument("--host", default=None)
    start.add_argument("--port", type=int, default=None)
    start.add_argument("--data-dir", default=None)
    start.add_argument("--registration-token", default=None)
    start.add_argument("--bootstrap-password", default=None)
    start.add_argument("--worker-name", default=None)
    start.add_argument("--worker-ip", default=None)
    start.add_argument("--worker-port", type=int, default=None,
                       help="worker HTTP port (0 = ephemeral; the worker "
                       "registers whatever port it actually bound)")
    start.add_argument("--disable-worker", action="store_true", default=None)
    start.add_argument("--fake-detector", default=None)
    start.add_argument("--force-platform", default=None)
    start.add_argument("--debug", action="store_true", default=None)
    start.add_argument(
        "--ha", action="store_true", default=None,
        help="multi-server HA: lease-based leader election over the "
        "shared database",
    )
    start.add_argument("--database-path", default=None)

    sub.add_parser("version", help="print version")

    migrate = sub.add_parser("migrate", help="apply DB migrations and exit")
    migrate.add_argument("--data-dir", default=None)
    migrate.add_argument("--config-file", default="")

    reset = sub.add_parser(
        "reset-admin-password", help="reset the admin password"
    )
    reset.add_argument("--data-dir", default=None)
    reset.add_argument("--password", required=True)
    reset.add_argument("--config-file", default="")

    reload_p = sub.add_parser(
        "reload-config",
        help="apply runtime-reloadable config to a live server "
        "(local admin auth from the data dir, like "
        "reset-admin-password)",
    )
    reload_p.add_argument("--data-dir", default=None)
    reload_p.add_argument("--config-file", default="")
    reload_p.add_argument(
        "--server", default="",
        help="server base URL (default http://127.0.0.1:<port> from "
        "config)",
    )
    reload_p.add_argument(
        "--set", action="append", default=[], dest="sets",
        metavar="FIELD=VALUE",
        help="set one reloadable field (repeatable)",
    )
    reload_p.add_argument(
        "--list", action="store_true",
        help="list the reloadable fields and exit",
    )

    pre = sub.add_parser(
        "preflight",
        help="pre-run checks: config, data dir, ports, detector, "
        "native tools, jax (the reference's prerun role without "
        "s6/container services)",
    )
    pre.add_argument("--config-file", default="")
    pre.add_argument("--data-dir", default=None)
    pre.add_argument("--host", default=None)
    pre.add_argument("--port", type=int, default=None)
    pre.add_argument("--worker-port", type=int, default=None)
    pre.add_argument("--fake-detector", default=None)
    pre.add_argument("--force-platform", default=None)
    pre.add_argument(
        "--skip-jax", action="store_true",
        help="skip the jax import/backend check (slow on cold caches)",
    )
    return p


def _config_from_args(args) -> "Config":
    from gpustack_tpu.config import Config

    overrides = {
        k: v
        for k, v in vars(args).items()
        if k not in ("command", "config_file") and v is not None
    }
    return Config.load(overrides, config_file=args.config_file or None)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if getattr(args, "debug", False) else logging.INFO,
        format=LOG_FORMAT,
    )
    if args.command == "version":
        from gpustack_tpu import __version__

        print(__version__)
        return 0
    if args.command == "migrate":
        from gpustack_tpu.orm.db import Database, run_migrations

        cfg = _config_from_args(args)
        db = Database(cfg.database_path)
        n = run_migrations(db)
        print(f"applied {n} migrations")
        db.close()
        return 0
    if args.command == "reset-admin-password":
        return _reset_admin_password(args)
    if args.command == "reload-config":
        return _reload_config(args)
    if args.command == "preflight":
        return _preflight(args)
    if args.command == "start":
        cfg = _config_from_args(args)
        if cfg.is_server:
            from gpustack_tpu.server.server import Server

            server = Server(cfg)
            try:
                asyncio.run(server.run_forever())
            except KeyboardInterrupt:
                pass
            return 0
        from gpustack_tpu.worker.worker import WorkerAgent

        agent = WorkerAgent(cfg)
        try:
            asyncio.run(agent.run_forever())
        except KeyboardInterrupt:
            pass
        return 0
    build_parser().print_help()
    return 1


def _reload_config(args) -> int:
    """Apply --set FIELD=VALUE pairs to a live server through
    /v2/config/reload, authenticating locally like reset-admin-password:
    the jwt secret + admin row in the data dir mint an admin session
    (reference cmd/reload_config.py local_auth pattern)."""
    import json as jsonlib
    import urllib.error
    import urllib.request

    from gpustack_tpu.api import auth as auth_mod
    from gpustack_tpu.orm.db import Database
    from gpustack_tpu.orm.record import Record
    from gpustack_tpu.schemas import User
    from gpustack_tpu.server.bus import EventBus

    cfg = _config_from_args(args)
    base = args.server or f"http://127.0.0.1:{cfg.port}"

    async def mint() -> str:
        db = Database(cfg.database_path)
        Record.bind(db, EventBus())
        # migrations BEFORE table creation: creating a fresh table under
        # a renamed kind while the old one still holds data would leave
        # the rename migration a conflicting copy to reconcile
        from gpustack_tpu.orm.db import run_migrations

        run_migrations(db)
        Record.create_all_tables(db)
        try:
            user = await User.first(username="admin")
            if user is None or not user.is_admin:
                raise SystemExit(
                    "no admin user in the database at "
                    f"{cfg.database_path}"
                )
            return auth_mod.issue_session_token(user, cfg.jwt_secret)
        finally:
            db.close()

    token = asyncio.run(mint())
    headers = {
        "Authorization": f"Bearer {token}",
        "Content-Type": "application/json",
    }

    def call(method: str, body=None):
        req = urllib.request.Request(
            f"{base}/v2/config/reload",
            data=jsonlib.dumps(body).encode() if body is not None else None,
            headers=headers, method=method,
        )
        try:
            with urllib.request.urlopen(req, timeout=15) as resp:
                return resp.status, jsonlib.loads(resp.read())
        except urllib.error.HTTPError as e:
            raw = e.read() or b"{}"
            try:
                return e.code, jsonlib.loads(raw)
            except jsonlib.JSONDecodeError:
                # non-JSON error page (reverse proxy, wrong service)
                raise SystemExit(
                    f"HTTP {e.code} from {base}: "
                    f"{raw[:200].decode(errors='replace')}"
                )
        except urllib.error.URLError as e:
            raise SystemExit(f"server unreachable at {base}: {e.reason}")

    if args.list or not args.sets:
        status, data = call("GET")
        print(jsonlib.dumps(data, indent=2))
        return 0 if status == 200 else 1
    body = {}
    for pair in args.sets:
        key, sep, value = pair.partition("=")
        if not sep:
            raise SystemExit(f"--set needs FIELD=VALUE, got {pair!r}")
        body[key.strip().replace("-", "_")] = value
    status, data = call("POST", body)
    print(jsonlib.dumps(data, indent=2))
    return 0 if status == 200 else 1


def _preflight(args) -> int:
    """Pre-run environment checks (reference cmd/prerun.py role — minus
    s6/postgres/gateway service rendering, which this design has no use
    for: no bundled service supervisor, sqlite state, in-process
    gateway)."""
    import os
    import socket

    cfg = _config_from_args(args)
    failures = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f" — {detail}" if detail else ""))
        if not ok:
            failures.append(name)

    print(f"preflight for data_dir={cfg.data_dir}")
    try:
        os.makedirs(cfg.data_dir, exist_ok=True)
        probe = os.path.join(cfg.data_dir, ".preflight")
        with open(probe, "w") as f:
            f.write("ok")
        os.unlink(probe)
        check("data dir writable", True)
    except OSError as e:
        check("data dir writable", False, str(e))

    for label, port in (
        ("server port", cfg.port),
        ("worker port", cfg.worker_port),
    ):
        if port == 0:
            check(f"{label} (ephemeral)", True)
            continue
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind((cfg.host if label == "server port" else "0.0.0.0",
                        port))
                check(f"{label} {port} free", True)
            except OSError as e:
                check(f"{label} {port} free", False, str(e))

    try:
        from gpustack_tpu.detectors import create_detector

        detector = create_detector(cfg.fake_detector or None)
        status = detector.detect()
        check("TPU detector", True, f"{len(status.chips)} chip(s)")
    except Exception as e:
        check("TPU detector", False, str(e))

    import shutil

    for tool in ("model-meta", "sysinfo"):
        path = shutil.which(tool) or (
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "native", "bin", tool,
            )
        )
        present = bool(path and os.path.exists(path))
        check(f"native tool {tool}", present,
              path if present else "not built (make -C native)")

    if not getattr(args, "skip_jax", False):
        # the one place outside an engine that opens a JAX backend:
        # preflight is a command of its own and never runs inside the
        # server or worker process, which must not hold the chips
        try:
            import jax

            if cfg.force_platform:
                jax.config.update("jax_platforms", cfg.force_platform)
            n = len(jax.devices())
            check("jax backend", True,
                  f"{jax.default_backend()} x{n}")
        except Exception as e:
            check("jax backend", False, str(e))

    if failures:
        print(f"preflight FAILED: {', '.join(failures)}")
        return 1
    print("preflight ok")
    return 0


def _reset_admin_password(args) -> int:
    from gpustack_tpu.api import auth as auth_mod
    from gpustack_tpu.orm.db import Database
    from gpustack_tpu.orm.record import Record
    from gpustack_tpu.schemas import User
    from gpustack_tpu.server.bus import EventBus

    cfg = _config_from_args(args)

    async def go():
        db = Database(cfg.database_path)
        Record.bind(db, EventBus())
        # migrations BEFORE table creation: creating a fresh table under
        # a renamed kind while the old one still holds data would leave
        # the rename migration a conflicting copy to reconcile
        from gpustack_tpu.orm.db import run_migrations

        run_migrations(db)
        Record.create_all_tables(db)
        user = await User.first(username="admin")
        if user is None:
            await User.create(
                User(
                    username="admin",
                    is_admin=True,
                    password_hash=auth_mod.hash_password(args.password),
                )
            )
        else:
            await user.update(
                password_hash=auth_mod.hash_password(args.password),
                require_password_change=False,
            )
        db.close()

    asyncio.run(go())
    print("admin password updated")
    return 0


if __name__ == "__main__":
    sys.exit(main())
