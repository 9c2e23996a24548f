"""Extra management routes: catalog, deploy-time evaluation, usage and
dashboard summaries.

Reference parity: model catalog (server/catalog.py), evaluate_models
deploy-time compatibility API (scheduler/evaluator.py:66), dashboard/usage
aggregation endpoints (routes/dashboard.py, routes/usage.py).
"""

from __future__ import annotations

import asyncio
import json
import logging
import time

import aiohttp
from aiohttp import web

from gpustack_tpu.observability.capture import CHILD_TIMEOUT_S
from gpustack_tpu.routes.crud import json_error
from gpustack_tpu.scheduler.calculator import (
    EvaluationError,
    chips_for_claim,
    evaluate_model,
)
from gpustack_tpu.schemas import (
    Model,
    ModelInstance,
    ModelInstanceState,
    Worker,
    WorkerState,
    validate_instance_transition,
)
from gpustack_tpu.server.catalog import get_catalog

logger = logging.getLogger(__name__)


from gpustack_tpu.utils.cache import locked_cached


@locked_cached(ttl=60.0)
async def _evaluate_cached(spec_json: str):
    """One evaluation per distinct spec per minute, concurrent callers
    coalesced (reference evaluator.py:56-62 TTL cache + rate limiter).
    Negative results cache too — a broken HF repo id polled by a UI must
    not re-probe the network every second. Returns ("ok", evaluation) or
    ("err", reason)."""
    spec = Model.model_validate(json.loads(spec_json))
    loop = asyncio.get_running_loop()
    try:
        evaluation = await loop.run_in_executor(
            None, evaluate_model, spec
        )
        return ("ok", evaluation)
    except EvaluationError as e:
        return ("err", str(e))


def add_extra_routes(app: web.Application) -> None:
    async def catalog(request: web.Request):
        return web.json_response(
            {"items": get_catalog(request.query.get("category", ""))}
        )

    async def evaluate(request: web.Request):
        """Deploy-time compatibility check: would this model spec fit the
        current fleet? (reference evaluator: evaluate_models).
        Admin-only: the verdict enumerates worker topology and free
        capacity, and only admins can act on it (deploys are gated)."""
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return json_error(400, "invalid JSON body")
        try:
            spec = Model.model_validate(body)
        except Exception as e:
            return json_error(400, f"invalid model spec: {e}")
        # key carries exactly the Model fields evaluation reads, under
        # their real names — the cached helper re-validates a Model from
        # this json
        cache_key = json.dumps(
            {
                "name": spec.name,
                "preset": spec.preset,
                "local_path": spec.local_path,
                "huggingface_repo_id": spec.huggingface_repo_id,
                "quantization": spec.quantization,
                "max_seq_len": spec.max_seq_len,
                "max_slots": spec.max_slots,
            },
            sort_keys=True,
        )
        status, evaluation = await _evaluate_cached(cache_key)
        if status == "err":
            return web.json_response(
                {"compatible": False, "reason": evaluation}
            )
        from gpustack_tpu.policies import filter_workers

        workers, drop_reasons = filter_workers(await Worker.all(), spec)
        if not workers:
            return web.json_response(
                {
                    "compatible": False,
                    "reason": (
                        "no eligible workers"
                        + (
                            f" ({'; '.join(drop_reasons[:4])})"
                            if drop_reasons else ""
                        )
                    ),
                }
            )
        from gpustack_tpu.scheduler.calculator import fleet_chip_budget

        max_single = max(w.total_chips for w in workers)
        max_chips, allowed_counts = fleet_chip_budget(
            workers, spec.distributable
        )
        hbm = min(w.hbm_per_chip for w in workers)
        try:
            claim = chips_for_claim(
                evaluation,
                hbm_per_chip=hbm,
                max_chips=max_chips,
                long_context=spec.max_seq_len >= 16384,
                explicit_plan=spec.mesh_plan,
                explicit_chips=spec.chips_per_replica,
                allowed_counts=allowed_counts,
            )
        except ValueError as e:      # malformed explicit mesh_plan
            return json_error(400, str(e))
        if claim is None:
            return web.json_response(
                {
                    "compatible": False,
                    "reason": (
                        f"needs ~{evaluation.total_bytes / 2**30:.1f} GiB; "
                        f"no fit within {max_chips} chips of "
                        f"{hbm / 2**30:.0f} GiB HBM"
                    ),
                }
            )
        return web.json_response(
            {
                "compatible": True,
                "claim": claim.model_dump(),
                "weight_gib": round(evaluation.weight_bytes / 2**30, 2),
                "kv_cache_gib": round(
                    evaluation.kv_cache_bytes / 2**30, 2
                ),
                "multi_host": claim.chips > max_single,
            }
        )

    async def usage_summary(request: web.Request):
        """Aggregated token usage by model and user (dashboard feed).

        Admins see every user; other users see only their own row;
        worker/system tokens are rejected.

        With ``?window=<N>h|<N>d`` the summary spans BOTH storage
        tiers: hot ``model_usage`` rows newer than the cutoff plus the
        cold ``usage_archive`` daily aggregates the UsageArchiver
        rolled older rows into — the query surface multi-tenant
        quota/billing work needs, since hot retention is only days."""
        from gpustack_tpu.orm.record import Record

        # shared admin/user visibility rule (same helper as the series
        # and top-N endpoints — one place to change scoping semantics)
        scope, params, err = _principal_scope(request)
        if err is not None:
            return err
        window = request.query.get("window", "")
        if window:
            return await _usage_summary_windowed(
                request, scope, params, window
            )
        db = Record.db()
        rows = await db.execute(
            "SELECT route_name AS route, "
            "COUNT(*) AS requests, "
            f"COALESCE(SUM({db.json_num('prompt_tokens')}), 0) AS pt, "
            f"COALESCE(SUM({db.json_num('completion_tokens')}), 0) "
            "AS ct "
            f"FROM model_usage WHERE 1=1{scope} "
            "GROUP BY route_name ORDER BY requests DESC",
            params,
        )
        by_user = await db.execute(
            "SELECT user_id, COUNT(*) AS requests, "
            f"COALESCE(SUM({db.json_num('total_tokens')}), 0) AS tok "
            f"FROM model_usage WHERE 1=1{scope} GROUP BY user_id",
            params,
        )
        return web.json_response(
            {
                "by_model": [
                    {
                        "route": r["route"],
                        "requests": r["requests"],
                        "prompt_tokens": int(r["pt"]),
                        "completion_tokens": int(r["ct"]),
                    }
                    for r in rows
                ],
                "by_user": [
                    {
                        "user_id": r["user_id"],
                        "requests": r["requests"],
                        "total_tokens": int(r["tok"]),
                    }
                    for r in by_user
                ],
            }
        )

    async def _usage_summary_windowed(
        request: web.Request, scope: str, params: list, window: str
    ):
        """Hot + cold usage over one window, per model and per user.

        Hot rows group on ``model_id`` (the archive has no route
        name), so both tiers merge on the same key. Days that straddle
        the cutoff are included whole from the archive side — daily
        aggregates cannot be split, and overcounting a partial first
        day beats silently dropping it."""
        import re as _re

        from gpustack_tpu.orm.record import Record

        # `window=24h|30d` is the ISSUE-specified surface for this
        # endpoint; it parses into hours and shares the cutoff
        # derivation with the `hours=` endpoints (_cutoff_hours_ago)
        m = _re.match(r"^(\d+(?:\.\d+)?)([hd])$", window.strip())
        if m is None:
            return json_error(
                400, "'window' must look like 24h or 30d"
            )
        hours = float(m.group(1)) * (24.0 if m.group(2) == "d" else 1.0)
        if not 0 < hours <= 24 * 400:
            return json_error(400, "'window' out of range")
        cutoff = _cutoff_hours_ago(hours)
        db = Record.db()

        by_model: dict = {}
        by_user: dict = {}

        def bucket(store: dict, key):
            return store.setdefault(key, {
                "requests": 0, "prompt_tokens": 0,
                "completion_tokens": 0, "total_tokens": 0,
                "archived_requests": 0,
            })

        hot = await db.execute(
            "SELECT model_id, user_id, COUNT(*) AS requests, "
            f"COALESCE(SUM({db.json_num('prompt_tokens')}), 0) AS pt, "
            f"COALESCE(SUM({db.json_num('completion_tokens')}), 0) "
            "AS ct, "
            f"COALESCE(SUM({db.json_num('total_tokens')}), 0) AS tok "
            f"FROM model_usage WHERE created_at >= ?{scope} "
            "GROUP BY model_id, user_id",
            [cutoff] + params,
        )
        cold = await db.execute(
            "SELECT model_id, user_id, "
            f"COALESCE(SUM({db.json_num('requests')}), 0) AS requests, "
            f"COALESCE(SUM({db.json_num('prompt_tokens')}), 0) AS pt, "
            f"COALESCE(SUM({db.json_num('completion_tokens')}), 0) "
            "AS ct, "
            f"COALESCE(SUM({db.json_num('total_tokens')}), 0) AS tok "
            f"FROM usage_archive WHERE day >= ?{scope} "
            "GROUP BY model_id, user_id",
            [cutoff[:10]] + params,
        )
        for rows, archived in ((hot, False), (cold, True)):
            for r in rows:
                requests = int(r["requests"])
                adds = {
                    "requests": requests,
                    "prompt_tokens": int(r["pt"]),
                    "completion_tokens": int(r["ct"]),
                    "total_tokens": int(r["tok"]),
                    "archived_requests": requests if archived else 0,
                }
                for store, key in (
                    (by_model, int(r["model_id"] or 0)),
                    (by_user, int(r["user_id"] or 0)),
                ):
                    agg = bucket(store, key)
                    for k, v in adds.items():
                        agg[k] += v
        return web.json_response({
            "window": {"hours": hours, "cutoff": cutoff},
            "by_model": [
                {"model_id": k, **v}
                for k, v in sorted(
                    by_model.items(),
                    key=lambda kv: -kv[1]["total_tokens"],
                )
            ],
            "by_user": [
                {"user_id": k, **v}
                for k, v in sorted(
                    by_user.items(),
                    key=lambda kv: -kv[1]["total_tokens"],
                )
            ],
        })

    async def dashboard(request: web.Request):
        """Cluster overview (reference routes/dashboard.py).
        Admin-only: fleet size, chip accounting and instance states
        are cluster-wide facts, not any one tenant's."""
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        workers = await Worker.all()
        instances = await ModelInstance.all()
        models = await Model.all()
        from gpustack_tpu.policies.allocatable import CLAIMING_STATES

        total_chips = sum(w.total_chips for w in workers)
        used_chips = 0
        inst_states: dict = {}
        for i in instances:
            inst_states[i.state.value] = inst_states.get(i.state.value, 0) + 1
            # same accounting the scheduler uses (policies/allocatable.py)
            if i.state in CLAIMING_STATES:
                used_chips += len(i.chip_indexes) + sum(
                    len(s.chip_indexes) for s in i.subordinate_workers
                )
        return web.json_response(
            {
                "workers": {
                    "total": len(workers),
                    "ready": sum(
                        1 for w in workers if w.state == WorkerState.READY
                    ),
                },
                "chips": {"total": total_chips, "used": used_chips},
                "models": len(models),
                "instances": inst_states,
            }
        )

    async def cluster_manifests(request: web.Request):
        """Ready-to-apply K8s join bundle for this cluster (reference
        routes/clusters.py get_cluster_manifests; admin-only — it embeds
        the registration token)."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.schemas import Cluster
        from gpustack_tpu.server.k8s import render_manifests

        if err := require_admin(request):
            return err
        cluster = await Cluster.get(int(request.match_info["id"]))
        if cluster is None:
            return json_error(404, "cluster not found")
        cfg = request.app["config"]
        server_url = cfg.external_url.rstrip("/") or (
            f"{request.scheme}://{request.host}"
        )
        yaml_text = render_manifests(
            server_url,
            cfg.registration_token,
            tpu_accelerator=request.query.get(
                "accelerator", "tpu-v5-lite-podslice"
            ),
            # worker_port=0 means "ephemeral" for the LOCAL embedded
            # worker; a k8s pod needs a concrete containerPort, so the
            # manifest falls back to the fixed default.
            worker_port=cfg.worker_port or 10151,
            tunnel=request.query.get("tunnel") in ("1", "true"),
        )
        return web.Response(
            text=yaml_text, content_type="application/yaml"
        )

    # ---- dashboard depth (reference routes/dashboard.py 741 LoC,
    # usage.py 1,179 LoC, resource_usage.py 1,412 LoC: time-series,
    # per-entity breakdowns, top-N) ------------------------------------

    def _principal_scope(request):
        """(where-fragment, params, err) applying per-user visibility."""
        principal = request.get("principal")
        if principal is None or (
            principal.kind != "user" and not principal.is_admin
        ):
            return "", [], json_error(403, "user token required")
        if principal.is_admin:
            return "", [], None
        return " AND user_id = ?", [principal.user.id], None

    def _cutoff_hours_ago(hours: float) -> str:
        import datetime as _dt

        return (
            _dt.datetime.now(_dt.timezone.utc)
            - _dt.timedelta(hours=hours)
        ).isoformat()

    def _window(request, default_hours=24, max_hours=24 * 90):
        try:
            hours = float(request.query.get("hours", default_hours))
        except ValueError:
            return None, json_error(400, "'hours' must be a number")
        if not 0 < hours <= max_hours:
            return None, json_error(
                400, f"'hours' must be in (0, {max_hours}]"
            )
        return _cutoff_hours_ago(hours), None

    async def usage_series(request: web.Request):
        """Token/request time series, bucketed by hour or day, optional
        per-route split (reference usage.py get_model_usage series)."""
        from gpustack_tpu.orm.record import Record

        scope, params, err = _principal_scope(request)
        if err is not None:
            return err
        cutoff, err = _window(request)
        if err is not None:
            return err
        bucket = request.query.get("bucket", "hour")
        if bucket not in ("hour", "day"):
            return json_error(400, "'bucket' must be hour|day")
        # ISO timestamps bucket by prefix: 13 chars = YYYY-MM-DDTHH,
        # 10 = YYYY-MM-DD (SUBSTR is dialect-generic)
        width = 13 if bucket == "hour" else 10
        route = request.query.get("route", "")
        route_clause = " AND route_name = ?" if route else ""
        db = Record.db()
        q = (
            f"SELECT SUBSTR(created_at, 1, {width}) AS ts, "
            "route_name AS route, COUNT(*) AS requests, "
            f"COALESCE(SUM({db.json_num('prompt_tokens')}), 0) "
            "AS pt, "
            f"COALESCE(SUM({db.json_num('completion_tokens')}), 0)"
            " AS ct "
            "FROM model_usage WHERE created_at >= ?"
            f"{scope}{route_clause} "
            "GROUP BY ts, route_name ORDER BY ts"
        )
        rows = await db.execute(
            q, [cutoff] + params + ([route] if route else [])
        )
        return web.json_response({
            "bucket": bucket,
            "series": [
                {
                    "ts": r["ts"],
                    "route": r["route"],
                    "requests": r["requests"],
                    "prompt_tokens": int(r["pt"]),
                    "completion_tokens": int(r["ct"]),
                    "total_tokens": int(r["pt"]) + int(r["ct"]),
                }
                for r in rows
            ],
        })

    async def top_models(request: web.Request):
        """Top-N routes by total tokens over the window (reference
        dashboard.py get_top_models)."""
        from gpustack_tpu.orm.record import Record

        scope, params, err = _principal_scope(request)
        if err is not None:
            return err
        cutoff, err = _window(request)
        if err is not None:
            return err
        try:
            limit = int(request.query.get("limit", 10))
        except ValueError:
            return json_error(400, "'limit' must be an integer")
        limit = max(1, min(100, limit))
        db = Record.db()
        rows = await db.execute(
            "SELECT route_name AS route, COUNT(*) AS requests, "
            f"COALESCE(SUM({db.json_num('total_tokens')}), 0) "
            "AS tok, "
            f"COALESCE(SUM({db.json_num('prompt_tokens')}), 0) "
            "AS pt, "
            f"COALESCE(SUM({db.json_num('completion_tokens')}), 0)"
            " AS ct "
            "FROM model_usage WHERE created_at >= ?"
            f"{scope} "
            "GROUP BY route_name ORDER BY tok DESC LIMIT ?",
            [cutoff] + params + [limit],
        )
        return web.json_response({
            "items": [
                {
                    "route": r["route"],
                    "requests": r["requests"],
                    "total_tokens": int(r["tok"]),
                    "prompt_tokens": int(r["pt"]),
                    "completion_tokens": int(r["ct"]),
                }
                for r in rows
            ],
        })

    async def usage_by_user(request: web.Request):
        """Per-user×operation breakdown over the window (admin-only —
        reference usage.py per-user tables)."""
        from gpustack_tpu.orm.record import Record
        from gpustack_tpu.routes.crud import require_admin

        err = require_admin(request)
        if err is not None:
            return err
        cutoff, err = _window(request)
        if err is not None:
            return err
        db = Record.db()
        rows = await db.execute(
            "SELECT user_id, "
            f"{db.json_text('operation')} AS op, "
            "COUNT(*) AS requests, "
            f"COALESCE(SUM({db.json_num('total_tokens')}), 0) "
            "AS tok "
            "FROM model_usage WHERE created_at >= ? "
            "GROUP BY user_id, op ORDER BY tok DESC",
            [cutoff],
        )
        return web.json_response({
            "items": [
                {
                    # index columns are stored TEXT; normalize for clients
                    "user_id": int(r["user_id"] or 0),
                    "operation": r["op"] or "",
                    "requests": r["requests"],
                    "total_tokens": int(r["tok"]),
                }
                for r in rows
            ],
        })

    async def worker_history(request: web.Request):
        """Fleet utilization time series from SystemLoad snapshots
        (reference resource_usage.py / system_load history; admin)."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.server.collectors import SystemLoad

        err = require_admin(request)
        if err is not None:
            return err
        cutoff, err = _window(request)
        if err is not None:
            return err
        # bound the response: a 90-day window over 60s samples is ~130k
        # rows — keep the NEWEST rows of the window (a dashboard without
        # current data is useless), then stride-sample to <=500 points
        samples = await SystemLoad.filter_created_after(
            cutoff, limit=20000, newest_first=True
        )
        samples.reverse()            # chronological for the client
        if len(samples) > 500:
            stride = len(samples) // 500 + 1
            # anchor the stride on the NEWEST sample (dashboards read
            # the last point as "current"), not the oldest
            samples = samples[::-1][::stride][::-1]
        return web.json_response({
            "series": [
                {
                    "ts": s.created_at,
                    "workers_total": s.workers_total,
                    "workers_ready": s.workers_ready,
                    "chips_total": s.chips_total,
                    "chips_allocated": s.chips_allocated,
                    "memory_used_bytes": s.memory_used_bytes,
                    "memory_total_bytes": s.memory_total_bytes,
                }
                for s in samples
            ],
        })

    # Runtime-updatable config fields (reference reload-config whitelist,
    # cmd/reload_config.py + utils/config.py WHITELIST_CONFIG_FIELDS):
    # only fields that are safe to change on a LIVE server — no listen
    # addresses, no secrets persisted elsewhere, no worker identity.
    RELOADABLE_FIELDS = (
        "debug",             # flips the root log level immediately
        "advertised_url",    # embedded in provisioned worker bootstrap
        "external_url",      # rendered into k8s manifests
        "registration_token",  # join-token rotation without restart
    )

    async def reload_config(request: web.Request):
        """Apply whitelisted config fields to the live server (reference
        reload-config server endpoint). Admin only; GET lists the
        whitelist, POST {field: value, ...} applies."""
        from gpustack_tpu.routes.crud import require_admin

        err = require_admin(request)
        if err is not None:
            return err
        cfg = request.app["config"]
        if request.method == "GET":
            return web.json_response({
                "reloadable": list(RELOADABLE_FIELDS),
                "current": {
                    f: getattr(cfg, f) for f in RELOADABLE_FIELDS
                    if f != "registration_token"   # never echo secrets
                },
            })
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return json_error(400, "invalid JSON body")
        if not isinstance(body, dict) or not body:
            return json_error(400, "body must be {field: value, ...}")
        rejected = [k for k in body if k not in RELOADABLE_FIELDS]
        if rejected:
            return json_error(
                400,
                f"not runtime-reloadable: {sorted(rejected)}; "
                f"allowed: {list(RELOADABLE_FIELDS)}",
            )
        # coerce EVERYTHING first, apply after: a bad value for a later
        # key must not leave earlier keys half-applied
        coerced_all = {}
        for key, value in body.items():
            field = type(cfg).model_fields[key]
            try:
                coerced_all[key] = pydantic_coerce(
                    field.annotation, value
                )
            except (TypeError, ValueError) as e:
                return json_error(400, f"bad value for {key!r}: {e}")
        applied = {}
        for key, coerced in coerced_all.items():
            setattr(cfg, key, coerced)
            applied[key] = (
                "<set>" if key == "registration_token" else coerced
            )
        if "debug" in body:
            import logging as _logging

            _logging.getLogger().setLevel(
                _logging.DEBUG if cfg.debug else _logging.INFO
            )
        if "registration_token" in coerced_all:
            await _propagate_registration_token(
                request.app, coerced_all["registration_token"]
            )
        if "advertised_url" in coerced_all:
            _propagate_advertised_url(
                request.app, coerced_all["advertised_url"]
            )
        logger.info("config reloaded: %s", applied)
        return web.json_response({"applied": applied})

    async def _propagate_registration_token(app, token: str) -> None:
        """Rotation must reach every consumer of the token, not just the
        cfg object: worker-join validation checks the cluster row's hash
        (api/auth_routes.py), and the worker-pool controller bootstraps
        provisioned VMs with its own copy."""
        from gpustack_tpu.api.auth import hash_secret
        from gpustack_tpu.schemas import Cluster

        for cluster in await Cluster.filter(name="default"):
            await cluster.update(
                registration_token_hash=hash_secret(token)
            )
        for ctrl in app.get("controllers", []):
            if hasattr(ctrl, "registration_token"):
                ctrl.registration_token = token
        # persist so a restart keeps the rotated token instead of
        # resurrecting the old one from the data dir. (A deployment that
        # passes --registration-token explicitly re-wins on restart by
        # design — the flag is the operator's source of truth there.)
        cfg = app["config"]
        try:
            import os as _os

            path = _os.path.join(cfg.data_dir, "registration_token")

            def _persist() -> None:
                with open(path, "w") as f:
                    f.write(token)

            await asyncio.to_thread(_persist)
        except OSError:
            logger.warning("could not persist rotated token")

    def _propagate_advertised_url(app, url: str) -> None:
        for ctrl in app.get("controllers", []):
            if hasattr(ctrl, "server_url"):
                ctrl.server_url = url

    def pydantic_coerce(annotation, value):
        if annotation is bool:
            if isinstance(value, bool):
                return value
            if str(value).lower() in ("1", "true", "yes", "on"):
                return True
            if str(value).lower() in ("0", "false", "no", "off"):
                return False
            raise ValueError(f"not a boolean: {value!r}")
        if annotation is int:
            return int(value)
        if annotation is float:
            return float(value)
        return str(value)

    async def instance_drain(request: web.Request):
        """Graceful retirement of one replica (rolling updates): flips a
        RUNNING instance to DRAINING — the proxy's picker stops routing
        to it, the owning worker waits for in-flight requests to finish
        (bounded by its drain timeout), SIGTERMs the engine, and retires
        the row so replica sync creates a replacement. Admin-only."""
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        inst = await ModelInstance.get(int(request.match_info["id"]))
        if inst is None:
            return json_error(404, "instance not found")
        if inst.state == ModelInstanceState.DRAINING:
            return web.json_response(inst.model_dump(mode="json"))
        # the declared lifecycle (schemas/models.py) is the authority
        # on which states may drain — today only RUNNING -> DRAINING
        if not validate_instance_transition(
            inst.state, ModelInstanceState.DRAINING
        ):
            return json_error(
                409,
                f"instance is {inst.state.value}; only a running "
                "instance can drain",
            )
        await inst.update(
            state=ModelInstanceState.DRAINING,
            state_message="drain requested",
        )
        return web.json_response(inst.model_dump(mode="json"))

    app.router.add_post(
        "/v2/model-instances/{id:\\d+}/drain", instance_drain
    )

    async def model_rollout(request: web.Request):
        """Rollout status for one model: the active (or newest) plan
        with its batch history, gate snapshots and state, plus recent
        attempts (server/rollout.py). Admin-only."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.schemas import Rollout
        from gpustack_tpu.schemas.rollouts import (
            ACTIVE_ROLLOUT_STATES,
        )

        if err := require_admin(request):
            return err
        model = await Model.get(int(request.match_info["id"]))
        if model is None:
            return json_error(404, "model not found")
        rollouts = sorted(
            await Rollout.filter(model_id=model.id),
            key=lambda r: r.id,
        )
        active = [
            r for r in rollouts if r.state in ACTIVE_ROLLOUT_STATES
        ]
        instances = await ModelInstance.filter(model_id=model.id)
        return web.json_response({
            "model": model.name,
            "generation": model.generation,
            "instances": [
                {
                    "id": i.id,
                    "name": i.name,
                    "state": i.state.value,
                    "generation": i.generation,
                }
                for i in sorted(instances, key=lambda i: i.id)
            ],
            "active": (
                active[-1].model_dump(mode="json") if active else None
            ),
            "history": [
                r.model_dump(mode="json") for r in rollouts[-10:]
            ],
        })

    app.router.add_get("/v2/models/{id:\\d+}/rollout", model_rollout)

    async def model_rollback(request: web.Request):
        """Manually roll back the model's active rollout: restores the
        previous generation's archived spec and drains the new
        generation — the same path automatic gate failures take.
        409 when no rollout is mid-flight. Admin-only."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.schemas import Rollout, RolloutState
        from gpustack_tpu.schemas.rollouts import (
            ACTIVE_ROLLOUT_STATES,
        )

        if err := require_admin(request):
            return err
        model = await Model.get(int(request.match_info["id"]))
        if model is None:
            return json_error(404, "model not found")
        controller = request.app.get("rollout")
        if controller is None:
            return json_error(503, "rollout controller not running")
        rollout = await Rollout.active_for(model.id)
        if rollout is None:
            return json_error(
                409, f"no rollout in flight for model {model.name!r}"
            )
        coordinator = request.app.get("coordinator")
        is_leader = coordinator is None or coordinator.is_leader
        if rollout.state != RolloutState.ROLLING_BACK:
            if is_leader:
                instances = await ModelInstance.filter(
                    model_id=model.id
                )
                # shared with the automatic gate path: spec restore +
                # re-tag + new-generation teardown + incident record
                await controller.begin_rollback(
                    model, rollout, instances, time.time(),
                    "manual rollback requested",
                    event="manual_rollback",
                )
            elif not rollout.rollback_requested:
                # HA follower: executing here would strand the
                # incident + event counter in THIS process's in-memory
                # SLO ring where no operator looks — note the request
                # on the plan and let the leader's next reconcile tick
                # execute it. SQL-conditional on the indexed `state`
                # column: a fetch-then-save here could interleave with
                # the leader writing COMPLETED and resurrect the plan
                # from the stale snapshot (the leader polls the marker,
                # so skipping the event-bus publish is fine).
                still_forward = tuple(
                    s.value for s in ACTIVE_ROLLOUT_STATES
                    if s != RolloutState.ROLLING_BACK
                )
                qs = ",".join("?" * len(still_forward))
                setter = Rollout.db().json_set("rollback_requested")

                def _note(conn, _id=rollout.id, _states=still_forward):
                    cur = conn.execute(
                        f"UPDATE rollout SET data = {setter} "
                        f"WHERE id = ? AND state IN ({qs})",
                        # json_set binds JSON text on every dialect
                        (
                            json.dumps("manual rollback requested"),
                            _id, *_states,
                        ),
                    )
                    conn.commit()
                    return cur.rowcount

                # the leader's whole-document plan writes (_record)
                # can erase a marker that commits inside their
                # fetch->update window — verify the note survived and
                # re-land it (bounded) so the 202 acknowledgement
                # can't silently lose the rollback. Each _record
                # erasure needs the leader to take its plan lock, so
                # a couple of re-lands outlast any realistic race.
                for _ in range(5):
                    await Rollout.db().run(_note)
                    fresh = await Rollout.get(rollout.id)
                    if (
                        fresh is None
                        or fresh.rollback_requested
                        or fresh.state.value not in still_forward
                    ):
                        break
                    await asyncio.sleep(0.05)
            rollout = await Rollout.get(rollout.id) or rollout
        return web.json_response(
            rollout.model_dump(mode="json"), status=202
        )

    app.router.add_post(
        "/v2/models/{id:\\d+}/rollback", model_rollback
    )

    async def debug_invariants(request: web.Request):
        """Convergence-invariant report for production triage (the same
        checks the chaos harness runs — testing/invariants.py):
        `violations` must be empty on a healthy control plane at any
        instant; `eventual` entries persisting across calls point at
        the component that stopped converging. Admin-only."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.testing.invariants import (
            DEFAULT_STUCK_BOUND,
            control_plane_snapshot,
        )

        if err := require_admin(request):
            return err
        try:
            bound = float(
                request.query.get("stuck_bound", DEFAULT_STUCK_BOUND)
            )
        except ValueError:
            return json_error(400, "stuck_bound must be a number")
        return web.json_response(await control_plane_snapshot(bound))

    app.router.add_get("/v2/debug/invariants", debug_invariants)

    async def debug_traces(request: web.Request):
        """Recent request traces from the in-memory ring
        (observability/tracing.py): per-phase spans for every hop this
        process served — the server's auth/schedule/connect/ttft/stream
        decomposition, plus (embedded-worker mode) the worker relay's
        spans. Filterable by trace id / model / minimum duration.
        Admin-only."""
        from gpustack_tpu.observability import tracing
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.utils.profiling import STATS

        if err := require_admin(request):
            return err
        trace_id = request.query.get("trace_id", "").strip().lower()
        model = request.query.get("model", "")
        # phase= keeps traces that recorded a span with that name
        # (connect, ttft, kv_upload, …); outcome= matches the sealed
        # outcome (ok/error/…) — docs/OBSERVABILITY.md lists both
        phase = request.query.get("phase", "")
        outcome = request.query.get("outcome", "")
        try:
            min_ms = float(request.query.get("min_duration_ms", 0))
            limit = min(200, int(request.query.get("limit", 50)))
        except ValueError:
            return json_error(
                400, "min_duration_ms/limit must be numbers"
            )
        components = request.query.get("component", "")
        wanted = (
            [c for c in components.split(",") if c]
            or tracing.store_components()
        )
        items = []
        for component in wanted:
            items.extend(
                tracing.get_store(component).query(
                    trace_id=trace_id, model=model,
                    min_duration_ms=min_ms, phase=phase,
                    outcome=outcome, limit=limit,
                )
            )
        items.sort(key=lambda e: e.get("started_at", 0.0), reverse=True)
        return web.json_response(
            {
                "items": items[:limit],
                "components": tracing.store_components(),
                # slow-call accounting (utils/profiling @timed sites)
                # rides along: one triage endpoint for "where is the
                # time going" questions
                "slow_calls": STATS.snapshot(),
            }
        )

    app.router.add_get("/v2/debug/traces", debug_traces)

    async def debug_slo(request: web.Request):
        """Current SLO compliance, two-window burn rates, and alert
        state per model/objective (observability/slo.py, fed by
        server/sloeval.py). ``ok``/``warning``/``firing``/``resolved``
        here is the same state machine the
        ``gpustack_slo_alert_state`` gauge exports. Admin-only."""
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        evaluator = request.app.get("slo")
        if evaluator is None:
            return json_error(503, "slo evaluator not running")
        return web.json_response(evaluator.status())

    app.router.add_get("/v2/debug/slo", debug_slo)

    async def debug_incidents(request: web.Request):
        """Bounded incident ring: every alert episode with its state
        transitions and the correlated evidence snapshot captured at
        escalation (trace exemplars, lifecycle timelines, engine
        metrics, invariant report). Filterable by ``model=``,
        ``state=`` (open|resolved|closed) and ``since=`` (unix
        seconds). Admin-only."""
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        evaluator = request.app.get("slo")
        if evaluator is None:
            return json_error(503, "slo evaluator not running")
        state = request.query.get("state", "")
        if state and state not in ("open", "resolved", "closed"):
            return json_error(
                400, "state must be open|resolved|closed"
            )
        try:
            since = float(request.query.get("since", 0))
            limit = min(200, int(request.query.get("limit", 50)))
        except ValueError:
            return json_error(400, "since/limit must be numbers")
        return web.json_response({
            "items": evaluator.engine.incidents(
                model=request.query.get("model", ""),
                state=state, since=since, limit=limit,
            ),
        })

    app.router.add_get("/v2/debug/incidents", debug_incidents)

    async def debug_tenancy(request: web.Request):
        """Tenant QoS state (server/tenancy.py): per-tenant in-flight,
        admission/shed counters by reason, token-budget position and
        effective limits — hot tenants first, bounded. The triage
        surface for "who is the noisy neighbor". Admin-only."""
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        tenancy = request.app.get("tenancy")
        if tenancy is None:
            return json_error(503, "tenancy registry not mounted")
        try:
            limit = min(1000, int(request.query.get("limit", 100)))
        except ValueError:
            return json_error(400, "limit must be an integer")
        return web.json_response({
            "items": tenancy.snapshot(limit=limit),
            "evictions": tenancy.evictions,
            "model_cap": tenancy.model_cap,
            "fair_watermark": tenancy.fair_watermark,
        })

    app.router.add_get("/v2/debug/tenancy", debug_tenancy)

    # fleet rollup: which normalized series aggregate how. SUM gauges
    # add across a model's replicas; MAX gauges answer "worst replica";
    # RATE counters become per-second throughput between consecutive
    # calls (the first call has no window and reports null rates).
    FLEET_SUM_GAUGES = (
        "gpustack_tpu:requests_running",
        "gpustack_tpu:requests_waiting",
        "gpustack_tpu:slots_total",
        "gpustack_tpu:queue_depth",
        "gpustack_tpu:kv_cache_host_bytes",
        "gpustack_tpu:kv_blocks_used",
    )
    FLEET_MAX_GAUGES = (
        "gpustack_tpu:queue_oldest_wait_seconds",
        "gpustack_tpu:scrape_age_seconds",
        "gpustack_tpu:flight_overhead_ratio",
    )
    FLEET_COUNTERS = (
        "gpustack_tpu:prompt_tokens_total",
        "gpustack_tpu:generation_tokens_total",
        "gpustack_tpu:spec_proposed_total",
        "gpustack_tpu:spec_accepted_total",
        "gpustack_tpu:kv_cache_prefix_tokens_reused",
    )

    async def debug_fleet(request: web.Request):
        """Cluster-wide engine saturation rollup: scrapes every READY
        worker's /metrics (the normalized ``gpustack_tpu:*`` engine
        series the worker already aggregates), groups by model, and
        reports the signals a replica autoscaler consumes — tokens/s
        prefill vs decode, occupancy, queue wait, KV pressure, spec
        acceptance, and scrape staleness. Consistent by construction
        with each engine's own ``GET /debug/flight``: both read the
        same flight-recorder counters. Admin-only."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.server.fleet import (
            scrape_normalized_samples,
        )

        if err := require_admin(request):
            return err
        now = time.time()
        workers = [
            w for w in await Worker.filter(limit=None)
            if w.state == WorkerState.READY
        ]
        instances = await ModelInstance.filter(limit=None)
        inst_model = {str(i.id): i.model_name for i in instances}
        # one shared scrape pipeline with the SLO evaluator's
        # queue-wait feed (server/fleet.py) — the two surfaces read
        # identical samples by construction
        workers_out, samples = await scrape_normalized_samples(
            request.app, workers, inst_model
        )

        models_out: dict = {}
        for (model, iid), metrics in samples.items():
            model = model or "unknown"
            m = models_out.setdefault(model, {
                "instances": 0,
                "sums": {}, "maxes": {}, "counters": {},
                "per_instance": {},
            })
            m["instances"] += 1
            m["per_instance"][iid] = {
                k: v for k, v in sorted(metrics.items())
            }
            for name in FLEET_SUM_GAUGES:
                if name in metrics:
                    m["sums"][name] = (
                        m["sums"].get(name, 0.0) + metrics[name]
                    )
            for name in FLEET_MAX_GAUGES:
                if name in metrics:
                    m["maxes"][name] = max(
                        m["maxes"].get(name, 0.0), metrics[name]
                    )
            for name in FLEET_COUNTERS:
                if name in metrics:
                    m["counters"][name] = (
                        m["counters"].get(name, 0.0) + metrics[name]
                    )
            real = metrics.get(
                "gpustack_tpu:dispatched_tokens_total|real"
            )
            padded = metrics.get(
                "gpustack_tpu:dispatched_tokens_total|padded"
            )
            if real is not None and padded is not None:
                c = m["counters"]
                c["dispatched_real"] = (
                    c.get("dispatched_real", 0.0) + real
                )
                c["dispatched_padded"] = (
                    c.get("dispatched_padded", 0.0) + padded
                )

        # counter rates between consecutive calls (per-process cache)
        prev = request.app.setdefault("fleet_scrape_prev", {})

        def rate(model: str, metric: str, cur: float):
            entry = prev.get((model, metric))
            prev[(model, metric)] = (cur, now)
            if entry is None:
                return None
            last, ts = entry
            dt = now - ts
            if dt <= 0 or cur < last:   # reset (replica restart)
                return None
            return round((cur - last) / dt, 3)

        out_models = {}
        for model, m in sorted(models_out.items()):
            sums, maxes, counters = (
                m["sums"], m["maxes"], m["counters"]
            )
            slots = sums.get("gpustack_tpu:slots_total", 0.0)
            running = sums.get("gpustack_tpu:requests_running", 0.0)
            proposed = counters.get(
                "gpustack_tpu:spec_proposed_total", 0.0
            )
            accepted = counters.get(
                "gpustack_tpu:spec_accepted_total", 0.0
            )
            d_real = counters.get("dispatched_real")
            d_padded = counters.get("dispatched_padded")
            out_models[model] = {
                "instances": m["instances"],
                "slots_total": int(slots),
                "requests_running": int(running),
                "requests_waiting": int(
                    sums.get("gpustack_tpu:requests_waiting", 0.0)
                ),
                "occupancy": round(running / slots, 4) if slots else None,
                "queue_oldest_wait_seconds": round(
                    maxes.get(
                        "gpustack_tpu:queue_oldest_wait_seconds", 0.0
                    ), 3,
                ),
                "prefill_tokens_per_s": rate(
                    model, "prompt_tokens",
                    counters.get(
                        "gpustack_tpu:prompt_tokens_total", 0.0
                    ),
                ),
                "decode_tokens_per_s": rate(
                    model, "generation_tokens",
                    counters.get(
                        "gpustack_tpu:generation_tokens_total", 0.0
                    ),
                ),
                "prompt_tokens_total": int(counters.get(
                    "gpustack_tpu:prompt_tokens_total", 0.0
                )),
                "generation_tokens_total": int(counters.get(
                    "gpustack_tpu:generation_tokens_total", 0.0
                )),
                "spec_acceptance": (
                    round(accepted / proposed, 4) if proposed else None
                ),
                "padding_waste_pct": (
                    round(100.0 * (1.0 - d_real / d_padded), 2)
                    if d_padded else None
                ),
                "kv": {
                    "host_bytes": int(sums.get(
                        "gpustack_tpu:kv_cache_host_bytes", 0.0
                    )),
                    "blocks": int(sums.get(
                        "gpustack_tpu:kv_blocks_used", 0.0
                    )),
                    "prefix_tokens_reused": int(counters.get(
                        "gpustack_tpu:kv_cache_prefix_tokens_reused",
                        0.0,
                    )),
                },
                "scrape_age_seconds_max": round(
                    maxes.get("gpustack_tpu:scrape_age_seconds", 0.0),
                    3,
                ),
                "flight_overhead_ratio_max": maxes.get(
                    "gpustack_tpu:flight_overhead_ratio"
                ),
                "per_instance": m["per_instance"],
            }
        body = {
            "scraped_at": now,
            "workers": workers_out,
            "models": out_models,
        }
        # autoscaler view rides the fleet rollup: the decisions and
        # the signals they read belong on one surface
        autoscaler = request.app.get("autoscaler")
        if autoscaler is not None:
            body["autoscaler"] = autoscaler.status()
        return web.json_response(body)

    app.router.add_get("/v2/debug/fleet", debug_fleet)

    async def instance_profile_capture(request: web.Request):
        """Relay an on-demand profiler capture server → worker →
        engine: wraps N scheduler steps in ``jax.profiler.trace`` on
        the engine host (flight-records-only when that jax build has
        no profiler), writes the artifact under the instance's log
        dir, and returns its path plus the captured step summary.
        Admin-only."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.server.worker_request import worker_fetch

        if err := require_admin(request):
            return err
        inst = await ModelInstance.get(int(request.match_info["id"]))
        if inst is None:
            return json_error(404, "instance not found")
        worker = await Worker.get(inst.worker_id or 0)
        if worker is None:
            return json_error(
                409, "instance is not placed on a worker"
            )
        try:
            steps = int(request.query.get("steps", 20))
            timeout_s = min(
                120.0, float(request.query.get("timeout_s", 30.0))
            )
        except ValueError:
            return json_error(400, "steps/timeout_s must be numbers")
        if steps < 1:
            return json_error(400, "steps must be >= 1")
        path = (
            f"/v2/instances/{inst.id}/profile"
            f"?steps={steps}&timeout_s={timeout_s}"
        )
        try:
            # a capture blocks until its steps elapse — long budget,
            # never the control-retry tier (a retried POST would 409
            # on the capture-in-progress guard)
            resp = await worker_fetch(
                request.app, worker, "POST", path,
                timeout=timeout_s + 90 + CHILD_TIMEOUT_S,
            )
        except (
            aiohttp.ClientError, OSError, asyncio.TimeoutError,
        ) as e:
            return json_error(502, f"worker unreachable: {e}")
        try:
            raw = await resp.read()
        except (
            aiohttp.ClientError, OSError, asyncio.TimeoutError,
        ) as e:
            return json_error(502, f"worker unreachable: {e}")
        finally:
            resp.release()
        try:
            payload = json.loads(raw)
        except ValueError:
            payload = {"error": raw.decode(errors="replace")[:500]}
        return web.json_response(payload, status=resp.status)

    app.router.add_post(
        "/v2/model-instances/{id:\\d+}/profile",
        instance_profile_capture,
    )

    async def instance_timeline(request: web.Request):
        """Lifecycle timeline for one instance: how long it sat in each
        state (fed by the lossless bus tap — observability/lifecycle.py).
        Admin-only."""
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        instance_id = int(request.match_info["id"])
        tracker = request.app.get("lifecycle")
        if tracker is None:
            return json_error(503, "lifecycle tracker not running")
        timeline = tracker.timeline(instance_id)
        if timeline is None:
            # the row may exist but predate this server's tap
            if await ModelInstance.get(instance_id) is None:
                return json_error(404, "instance not found")
            return web.json_response(
                {"instance_id": instance_id, "entries": []}
            )
        return web.json_response(timeline)

    app.router.add_get(
        "/v2/model-instances/{id:\\d+}/timeline", instance_timeline
    )
    app.router.add_get("/v2/config/reload", reload_config)
    app.router.add_post("/v2/config/reload", reload_config)
    app.router.add_get("/v2/model-catalog", catalog)
    app.router.add_post("/v2/models/evaluate", evaluate)
    app.router.add_get("/v2/usage/summary", usage_summary)
    app.router.add_get("/v2/usage/series", usage_series)
    app.router.add_get("/v2/usage/by-user", usage_by_user)
    app.router.add_get("/v2/dashboard", dashboard)
    app.router.add_get("/v2/dashboard/top-models", top_models)
    app.router.add_get("/v2/dashboard/worker-history", worker_history)
    async def gateway_config(request: web.Request):
        """Ready-to-apply L7 front config (nginx/envoy) for this server
        (the reference's embedded Higress gateway role at the L7 layer —
        server/gateway.py explains the divergence). Admin-only."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.server.gateway import (
            FLAVORS,
            render_gateway_config,
        )

        err = require_admin(request)
        if err is not None:
            return err
        from gpustack_tpu.schemas import Cluster

        cluster = await Cluster.get(int(request.match_info["id"]))
        if cluster is None:
            return json_error(404, "cluster not found")
        flavor = request.query.get("flavor", "nginx")
        if flavor not in FLAVORS:
            return json_error(
                400, f"'flavor' must be one of {list(FLAVORS)}"
            )
        cfg = request.app["config"]
        host = request.query.get("upstream_host") or (
            "127.0.0.1" if cfg.host in ("0.0.0.0", "::") else cfg.host
        )
        try:
            text = render_gateway_config(
                flavor, host, cfg.port,
                server_name=request.query.get("server_name", "_"),
            )
        except ValueError as e:
            return json_error(400, str(e))
        return web.Response(text=text, content_type="text/plain")

    async def observability_config(request: web.Request):
        """Prometheus scrape config + Grafana dashboard for this cluster
        (reference cmd/start.py:299-334 embeds the binaries; here the
        render-don't-bundle pattern — server/observability.py). Worker
        scrape targets come from the live fleet. Admin-only."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.schemas import Cluster, Worker
        from gpustack_tpu.server.observability import (
            render_observability_bundle,
        )

        from gpustack_tpu.server.observability import hostport

        err = require_admin(request)
        if err is not None:
            return err
        cluster = await Cluster.get(int(request.match_info["id"]))
        if cluster is None:
            return json_error(404, "cluster not found")
        cfg = request.app["config"]
        # ?server_host= override (same contract as gateway-config's
        # upstream_host): Prometheus usually runs on another machine,
        # where a 127.0.0.1 fallback would scrape ITSELF
        server_host = request.query.get("server_host") or (
            "127.0.0.1" if cfg.host in ("0.0.0.0", "::") else cfg.host
        )
        workers = await Worker.filter(cluster_id=cluster.id)
        targets = sorted(
            hostport(w.ip or "127.0.0.1", w.port)
            for w in workers if w.port
        )
        return web.json_response(
            render_observability_bundle(
                hostport(server_host, cfg.port), targets
            )
        )

    app.router.add_get(
        "/v2/clusters/{id:\\d+}/manifests", cluster_manifests
    )
    app.router.add_get(
        "/v2/clusters/{id:\\d+}/gateway-config", gateway_config
    )
    app.router.add_get(
        "/v2/clusters/{id:\\d+}/observability-config",
        observability_config,
    )

    # ---- multi-server tunnel federation (tunnel/federation.py;
    # reference websocket_proxy/main.py peers + patricia_trie routing)

    async def federation_peers(request: web.Request):
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        reg = request.app["federation"]
        return web.json_response(
            {"items": [p.to_public() for p in reg.peers()]}
        )

    async def federation_peer_upsert(request: web.Request):
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.tunnel.federation import FederationPeer

        if err := require_admin(request):
            return err
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return json_error(400, "invalid JSON body")
        if not isinstance(body, dict):
            return json_error(400, "body must be a JSON object")
        name = str(body.get("name", "")).strip()
        url = str(body.get("url", "")).strip()
        cidrs = body.get("cidrs", [])
        if not name or not url or not isinstance(cidrs, list):
            return json_error(
                400, "'name', 'url' and 'cidrs' (list) are required"
            )
        peer = FederationPeer(
            name, url, str(body.get("token", "")),
            [str(c) for c in cidrs],
        )
        try:
            request.app["federation"].upsert(peer)
        except ValueError as e:
            return json_error(400, f"invalid CIDR: {e}")
        return web.json_response(peer.to_public(), status=201)

    async def federation_peer_delete(request: web.Request):
        from gpustack_tpu.routes.crud import require_admin

        if err := require_admin(request):
            return err
        if not request.app["federation"].remove(
            request.match_info["name"]
        ):
            return json_error(404, "peer not found")
        return web.json_response({"deleted": True})

    async def federation_forward(request: web.Request):
        """Peer-side hop: replay a worker-bound request through THIS
        server's own worker path (tunnel or direct). Loop-protected —
        a forwarded request never re-federates."""
        from gpustack_tpu.routes.crud import require_admin
        from gpustack_tpu.schemas import Worker
        from gpustack_tpu.server.worker_request import worker_fetch

        if err := require_admin(request):
            return err
        if request.headers.get("X-GPUStack-Federated") != "1":
            # the hop marker is mandatory protocol surface: it is how a
            # peer knows this request already federated once, and it
            # backs the allow_federation=False guard below
            return json_error(
                400, "not a federation hop (X-GPUStack-Federated "
                "header missing)"
            )
        worker_ip = request.headers.get("X-GPUStack-Worker-Ip", "")
        worker_port = request.headers.get("X-GPUStack-Worker-Port", "")
        method = request.headers.get("X-GPUStack-Forward-Method", "GET")
        path = request.headers.get("X-GPUStack-Forward-Path", "")
        if not worker_ip or not path.startswith("/"):
            return json_error(
                400,
                "X-GPUStack-Worker-Ip and X-GPUStack-Forward-Path "
                "headers are required",
            )
        # ip AND port: multi-worker hosts share an IP across workers
        # with distinct ports/secrets/tunnels
        lookup = {"ip": worker_ip}
        if worker_port.isdigit():
            lookup["port"] = int(worker_port)
        worker = await Worker.first(**lookup)
        if worker is None:
            return json_error(
                502,
                f"no worker at {worker_ip}:{worker_port or '*'} on "
                "this server",
            )
        body = await request.read()
        try:
            resp = await worker_fetch(
                request.app, worker, method, path,
                raw_body=body,
                content_type=request.headers.get("Content-Type", ""),
                allow_federation=False,     # never hop twice
            )
        except aiohttp.ClientError as e:
            return json_error(502, f"worker unreachable via peer: {e}")
        out = web.StreamResponse(status=resp.status)
        # stamp: this response came from the WORKER path, not the
        # peer's own control plane — the originating server keys the
        # hop-failed-vs-worker-answered decision off it
        out.headers["X-GPUStack-Forwarded"] = "1"
        ct = resp.content_type
        if ct:
            out.content_type = ct
        await out.prepare(request)
        try:
            async for chunk in resp.content.iter_any():
                await out.write(chunk)
        finally:
            resp.release()
        return out

    app.router.add_get("/v2/federation/peers", federation_peers)
    app.router.add_post("/v2/federation/peers", federation_peer_upsert)
    app.router.add_delete(
        "/v2/federation/peers/{name}", federation_peer_delete
    )
    app.router.add_post("/v2/federation/forward", federation_forward)
