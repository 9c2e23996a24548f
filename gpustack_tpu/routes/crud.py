"""Generic CRUD + watch routes for any Record type.

One factory replaces the reference's per-resource route modules where those
are mechanical (list/get/create/update/delete + HTTP watch). Resources with
extra behavior (API keys, workers, models) layer custom handlers on top.

Watch protocol: ``GET /v2/<kind>?watch=true`` streams NDJSON events
(CREATED/UPDATED/DELETED/HEARTBEAT/RESYNC) — the reference's ActiveRecord
``streaming()`` equivalent (mixins/active_record.py:840).
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Callable, Optional, Type

import pydantic
from aiohttp import web

from gpustack_tpu.orm.record import Record
from gpustack_tpu.server.bus import EventType

logger = logging.getLogger(__name__)


def json_error(status: int, message: str) -> web.Response:
    return web.json_response({"error": message}, status=status)


def require_admin(request: web.Request) -> Optional[web.Response]:
    principal = request.get("principal")
    if principal is None or not principal.is_admin:
        return json_error(403, "admin privileges required")
    return None


def default_worker_owns(principal, obj, new_fields) -> bool:
    """A worker owns records that are unassigned (claimable) or its own.

    ``obj`` is None for creates; ``new_fields`` is the incoming field dict
    (None for deletes). Resources with stricter semantics (model
    instances) pass their own checker to add per-field restrictions.
    """
    if obj is not None and getattr(obj, "worker_id", 0) not in (
        None, 0, principal.worker_id
    ):
        return False
    if new_fields and new_fields.get("worker_id") not in (
        None, 0, principal.worker_id
    ):
        return False
    return True


# app key: the tasks of the open watch streams
WATCH_STREAMS = "watch_streams"


def add_crud_routes(
    app: web.Application,
    cls: Type[Record],
    path: str,
    *,
    create_hook: Optional[Callable] = None,
    update_hook: Optional[Callable] = None,
    delete_hook: Optional[Callable] = None,
    readonly: bool = False,
    admin_write: bool = True,
    worker_write: bool = False,
    admin_read: bool = False,
    redact: tuple = (),
    worker_owns: Callable = default_worker_owns,
    visible: Optional[Callable] = None,
) -> None:
    """Mount list/get/watch/create/update/delete for one Record type.

    Write access (reference confines mutation to admins and each worker's
    own records — routes/routes.py admin routers + worker auth):
      - ``admin_write=True`` (default): creates/updates/deletes require an
        admin (or system) principal.
      - ``worker_write=True``: additionally let WORKER principals write,
        but only records they own per ``worker_owns`` (unassigned records
        are claimable — the benchmark/model-file claim pattern), and they
        can never assign a record to a different worker.
    Read access: ``admin_read=True`` restricts list/get/watch to admins
    (user records). ``redact`` strips fields (e.g. password_hash) from
    every serialized response including watch payloads. ``visible`` is an
    optional ``async (request, obj) -> bool`` tenancy filter applied to
    list/get and to watch events that carry data (reference TenantContext
    role, api/tenant.py).
    """
    base = f"/v2/{path}"

    def dump(obj: Record) -> dict:
        data = obj.model_dump(mode="json")
        for field in redact:
            data.pop(field, None)
        return data

    app.setdefault(WATCH_STREAMS, set())

    def check_read(request: web.Request) -> Optional[web.Response]:
        if admin_read and (err := require_admin(request)):
            return err
        return None

    def check_write(
        request: web.Request, existing, new_fields: Optional[dict]
    ) -> Optional[web.Response]:
        principal = request.get("principal")
        if principal is None:
            return json_error(401, "authentication required")
        if not admin_write and not worker_write:
            return None
        if principal.is_admin:
            return None
        if worker_write and principal.kind == "worker":
            if not worker_owns(principal, existing, new_fields):
                return json_error(
                    403, f"worker token may not write this {path} record"
                )
            return None
        return json_error(403, "admin privileges required")

    async def list_or_watch(request: web.Request):
        if err := check_read(request):
            return err
        if request.query.get("watch") in ("true", "1"):
            return await watch(request)
        filters = {}
        for key, value in request.query.items():
            if key in ("limit", "offset", "watch", "since_id"):
                continue
            if key in cls.model_fields:
                filters[key] = value
        try:
            limit = int(request.query.get("limit", 100))
            offset = int(request.query.get("offset", 0))
            # keyset cursor (id > since_id, id order): list_all pages
            # with this instead of OFFSET so a row deleted between
            # pages can never shift a live row out of the result set
            since_id = request.query.get("since_id")
            since_id = int(since_id) if since_id is not None else None
        except ValueError:
            return json_error(
                400, "limit/offset/since_id must be integers"
            )
        if visible is None:
            items = await cls.filter(
                limit=limit, offset=offset, since_id=since_id,
                **filters,
            )
            total = await cls.count(**filters)
        else:
            # tenancy filter BEFORE pagination: pages must be full and
            # total must count only what this principal can see (a global
            # total would leak the number of hidden cross-tenant records)
            all_items = await cls.filter(
                limit=None, since_id=since_id, **filters
            )
            kept = []
            for item in all_items:
                if await visible(request, item):
                    kept.append(item)
            total = len(kept)
            items = kept[offset:offset + limit]
        return web.json_response(
            {
                "items": [dump(i) for i in items],
                "pagination": {
                    "total": total,
                    "limit": limit,
                    "offset": offset,
                },
            }
        )

    async def watch(request: web.Request):
        resp = web.StreamResponse(
            headers={"Content-Type": "application/x-ndjson"}
        )
        await resp.prepare(request)
        agen = cls.subscribe(send_initial=True, heartbeat=15.0)
        # a watch never ends by itself: shutdown cancels these instead
        # of waiting out the runner's grace period (server.py _shutdown)
        streams = request.app[WATCH_STREAMS]
        task = asyncio.current_task()
        streams.add(task)
        try:
            async for event in agen:
                if (
                    visible is not None
                    and isinstance(event.data, dict)
                ):
                    try:
                        obj = cls.model_validate(event.data)
                    except pydantic.ValidationError:
                        # fail CLOSED: an unparseable payload must not
                        # bypass the tenancy filter
                        continue
                    if not await visible(request, obj):
                        continue
                wire = event.to_wire()
                if redact:
                    # to_wire aliases the Event's own dicts and the bus
                    # hands one Event to every subscriber — copy before
                    # popping or redaction corrupts other subscribers.
                    for key in ("data", "changes"):
                        if isinstance(wire.get(key), dict):
                            wire[key] = {
                                k: v for k, v in wire[key].items()
                                if k not in redact
                            }
                await resp.write(
                    (json.dumps(wire) + "\n").encode()
                )
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        finally:
            streams.discard(task)
            await agen.aclose()
        return resp

    async def get_one(request: web.Request):
        if err := check_read(request):
            return err
        obj = await cls.get(int(request.match_info["id"]))
        if obj is None:
            return json_error(404, f"{path} not found")
        if visible is not None and not await visible(request, obj):
            # same 404 as nonexistence: no id oracle across tenants
            return json_error(404, f"{path} not found")
        return web.json_response(dump(obj))

    async def create(request: web.Request):
        # role-gate before parsing: unauthorized principals get a uniform
        # 403, never validation-error detail on attacker-controlled input
        if err := check_write(request, None, None):
            return err
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return json_error(400, "invalid JSON body")
        try:
            obj = cls.model_validate(body)
        except pydantic.ValidationError as e:
            return json_error(400, str(e))
        if err := check_write(request, None, body):
            return err
        obj.id = 0
        if create_hook:
            err = await create_hook(request, obj, body)
            if err is not None:
                return err
        await cls.create(obj)
        return web.json_response(dump(obj), status=201)

    async def update(request: web.Request):
        # role-gate before the fetch: a 404-vs-403 difference would give
        # unauthorized principals an id-existence oracle
        if err := check_write(request, None, None):
            return err
        obj = await cls.get(int(request.match_info["id"]))
        if obj is None:
            return json_error(404, f"{path} not found")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return json_error(400, "invalid JSON body")
        fields = {
            k: v for k, v in body.items()
            if k in cls.model_fields and k not in ("id", "created_at")
        }
        if err := check_write(request, obj, fields):
            return err
        # validate merged doc before persisting
        merged = obj.model_dump()
        merged.update(fields)
        try:
            validated = cls.model_validate(merged)
        except pydantic.ValidationError as e:
            return json_error(400, str(e))
        if update_hook:
            before = dict(fields)
            err = await update_hook(request, obj, fields)
            if err is not None:
                return err
            if fields != before:
                # the hook may canonicalize or add server-owned fields
                # (e.g. the model hook bumps `generation` on serving
                # changes) — re-validate so the write sees them
                merged = obj.model_dump()
                merged.update(fields)
                try:
                    validated = cls.model_validate(merged)
                except pydantic.ValidationError as e:
                    return json_error(400, str(e))
        # CAS write loop: Record.update persists the WHOLE document and
        # the hook awaited (queries, revision archives) since `obj` was
        # read. Only fields whose CURRENT value still matches the
        # snapshot the hook validated against may be written: e.g. the
        # instance transition hook judged old-state -> new-state legal
        # on `obj` — if the rescuer parked the row UNREACHABLE during
        # the hook's awaits, writing the approved state would persist a
        # transition nobody validated. An honest 409 lets the caller
        # re-read and re-decide. The write itself is CAS-guarded
        # (orm/record.py), so the old fetch→write gap is GONE: an
        # unrelated field moving in that instant surfaces as
        # ConflictError and we simply re-read and retry, while a
        # validated-field conflict keeps its per-field 409.
        from gpustack_tpu.orm.record import ConflictError

        for _attempt in range(3):
            fresh = await cls.get(obj.id)
            if fresh is None:
                return json_error(404, f"{path} not found")
            conflicts = sorted(
                k for k in fields
                if getattr(fresh, k) != getattr(obj, k)
            )
            if conflicts:
                return json_error(
                    409,
                    f"{path} field(s) {', '.join(conflicts)} changed "
                    "concurrently; retry",
                )
            try:
                await fresh.update(
                    _retries=0,
                    **{k: getattr(validated, k) for k in fields},
                )
            except ConflictError:
                continue
            return web.json_response(dump(fresh))
        return json_error(
            409, f"{path} changed concurrently; retry"
        )

    async def delete(request: web.Request):
        if err := check_write(request, None, None):
            return err
        obj = await cls.get(int(request.match_info["id"]))
        if obj is None:
            return json_error(404, f"{path} not found")
        if err := check_write(request, obj, None):
            return err
        if delete_hook:
            err = await delete_hook(request, obj)
            if err is not None:
                return err
        await obj.delete()
        return web.json_response({"deleted": obj.id})

    app.router.add_get(base, list_or_watch)
    app.router.add_get(base + "/{id:\\d+}", get_one)
    if not readonly:
        app.router.add_post(base, create)
        app.router.add_put(base + "/{id:\\d+}", update)
        app.router.add_patch(base + "/{id:\\d+}", update)
        app.router.add_delete(base + "/{id:\\d+}", delete)
