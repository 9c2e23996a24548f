"""Multi-host serving: leader→follower command broadcast.

Multi-controller JAX is SPMD: every process of a ``jax.distributed``
cluster must execute the SAME jitted programs in the SAME order or the
collectives hang. But only the leader's API server receives requests —
so the leader broadcasts each device-op it is about to run (prefill,
first-token sample, insert, decode, deactivate) over a TCP command
channel, and follower processes replay the identical call sequence on
their own runner. This is the role Ray's driver/worker actors play for
the reference's multinode vLLM (reference worker/backends/vllm.py:
258-328 bootstraps Ray for exactly this); here it is ~200 lines of
stdlib sockets + ndjson because the op vocabulary is tiny.

Determinism contract:
- A draw's key rides the wire as the two words the leader's programs
  take (``runner.draw_words``: the engine's seed and its count of
  draws) — followers never count draws themselves, so leader/follower
  sampling programs see bit-identical key inputs.
- Device arrays never ride the wire. A follower's ``prefill`` output is
  registered locally and consumed by its next ``insert`` — the engine's
  scheduling loop is single-threaded, so prefill→insert order is stable.
- Chunked prefill IS supported multi-host: the chunk schedule is
  deterministic host-side arithmetic, so chunk_start/chunk_continue/
  chunk_commit ops replay it with a dedicated follower register (no
  device arrays on the wire).
- Features whose host round-trips genuinely diverge across processes
  (host KV cache — leader-RAM contents with a nondeterministic async
  copy worker; speculative decoding; embeddings; VLM overrides) are
  disabled at command build for multi-host placements
  (worker/backends.py) and rejected here defensively.

The channel binds ``coordinator_port + 1`` on the leader host (the
scheduler allocates coordinator ports in even-aligned pairs so the +1 is
fenced too).
"""

from __future__ import annotations

import json
import logging
import socket
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from gpustack_tpu.engine.runner import draw_words

logger = logging.getLogger(__name__)

_CONNECT_TIMEOUT_S = 600.0   # follower hosts may still be downloading


def _key_data_list(key) -> List[int]:
    return np.asarray(draw_words(key)).tolist()


def _key_from_list(data: List[int]):
    return np.asarray(data, np.uint32)


def channel_token() -> str:
    """Shared command-channel auth token for this replica.

    GPUSTACK_TPU_CMD_TOKEN is injected into every process of a
    multi-host placement by the worker (worker/backends.py) — leader and
    followers therefore derive the SAME value with no extra rendezvous.
    Empty means auth is disabled (hand-launched processes without the
    env; the e2e tests always set it)."""
    import os

    return os.environ.get("GPUSTACK_TPU_CMD_TOKEN", "")


class CommandLeader:
    """Leader side: accepts follower connections, broadcasts op lines.

    Connections must open with ``AUTH <token>\\n`` (advisor r4: the
    channel carries every request's prompt token ids, and an
    unauthenticated early connection could permanently consume a
    follower slot, wedging the replica until the broadcast timeout).
    Failed handshakes are closed WITHOUT counting toward n_followers and
    the accept loop keeps going, so a port-scanner can't starve the real
    followers out of the rendezvous."""

    _HANDSHAKE_TIMEOUT_S = 10.0

    def __init__(
        self, port: int, n_followers: int, host: str = "0.0.0.0",
        token: Optional[str] = None,
    ):
        self.n_followers = n_followers
        self.token = channel_token() if token is None else token
        self._conns: List[socket.socket] = []
        self._lock = threading.Lock()
        self._ready = threading.Event()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(n_followers)
        threading.Thread(
            target=self._accept_loop, name="mh-accept", daemon=True
        ).start()

    def _handshake(self, conn: socket.socket, addr) -> None:
        """Admit ``conn`` iff its first line is the right AUTH; runs in
        its own thread so a stalled client can't block the accept loop."""
        try:
            conn.settimeout(self._HANDSHAKE_TIMEOUT_S)
            buf = b""
            while b"\n" not in buf and len(buf) < 512:
                chunk = conn.recv(256)
                if not chunk:
                    break
                buf += chunk
            line = buf.split(b"\n", 1)[0].decode(errors="replace").strip()
            # .strip() both sides: with auth disabled (empty token) the
            # follower sends "AUTH \n" which strips to "AUTH"
            if line != f"AUTH {self.token}".strip():
                logger.warning(
                    "rejecting command-channel connection from %s "
                    "(bad handshake)", addr,
                )
                conn.close()
                return
            conn.settimeout(None)
        except OSError:
            try:
                conn.close()
            except OSError:
                pass
            return
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        logger.info("follower connected from %s", addr)
        with self._lock:
            if len(self._conns) >= self.n_followers:
                conn.close()            # late duplicate
                return
            self._conns.append(conn)
            if len(self._conns) >= self.n_followers:
                self._ready.set()

    def _accept_loop(self) -> None:
        while not self._ready.is_set():
            try:
                conn, addr = self._srv.accept()
            except OSError:
                return
            threading.Thread(
                target=self._handshake, args=(conn, addr),
                name="mh-handshake", daemon=True,
            ).start()

    def broadcast(self, op: Dict[str, Any]) -> None:
        """Send one op to every follower; blocks until all are connected
        (ops before rendezvous would be lost, and the collectives they
        guard would hang anyway)."""
        if not self._ready.wait(_CONNECT_TIMEOUT_S):
            raise RuntimeError(
                f"only {len(self._conns)}/{self.n_followers} follower "
                "hosts connected to the command channel"
            )
        line = (json.dumps(op) + "\n").encode()
        with self._lock:
            for conn in self._conns:
                try:
                    conn.sendall(line)
                except OSError as e:
                    # the dead follower's absence will surface as this
                    # process's collectives failing; the serve manager's
                    # health monitor handles the teardown
                    logger.error("follower send failed: %s", e)

    def close(self) -> None:
        with self._lock:
            for c in self._conns:
                try:
                    c.close()
                except OSError:
                    pass
            self._conns.clear()
        try:
            self._srv.close()
        except OSError:
            pass


class BroadcastingRunner:
    """Wraps the leader's ModelRunner: every replayable device op is
    broadcast to the followers before running locally."""

    # insert() serializes its args onto the follower command channel
    # (ints on the wire), so the engine's dispatch-ahead admission must
    # NOT hand it a device-scalar first token. Class attr (not
    # __getattr__-delegated) so the wrapped runner's True never leaks.
    supports_async_insert = False
    # every device call is an op on the wire that the followers replay,
    # one for one: the engine switches a finished slot off at its finish
    # (:meth:`deactivate`), where for a local runner it hands the next
    # decode step a mask
    replays = True

    def __init__(self, runner, leader: CommandLeader):
        self._runner = runner
        self._leader = leader

    def __getattr__(self, name):
        # everything not explicitly wrapped delegates (bucket_for,
        # mesh, new_state, prefill_buckets, ...)
        return getattr(self._runner, name)

    # -- wrapped ops ------------------------------------------------------

    def prefill(self, token_ids, true_len: int):
        self._leader.broadcast({
            "op": "prefill",
            "ids": [int(t) for t in token_ids],
            "true_len": int(true_len),
        })
        return self._runner.prefill(token_ids, true_len)

    def sample_first(
        self, last_logits, temperature, top_k, top_p, seed, seeded,
        position, key, logit_bias=None,
    ):
        self._leader.broadcast({
            "op": "sample_first",
            "temperature": float(temperature), "top_k": int(top_k),
            "top_p": float(top_p), "seed": int(seed),
            "seeded": bool(seeded), "position": int(position),
            "key": _key_data_list(key),
            "logit_bias": (
                {str(k): float(v) for k, v in logit_bias.items()}
                if logit_bias else None
            ),
        })
        return self._runner.sample_first(
            last_logits, temperature, top_k, top_p, seed, seeded,
            position, key, logit_bias,
        )

    def insert(
        self, state, k, v, slot, true_len, first_token,
        temperature, top_k, top_p, seed=0, seeded=False, logit_bias=None,
    ):
        self._leader.broadcast({
            "op": "insert", "slot": int(slot), "true_len": int(true_len),
            "first_token": int(first_token),
            "temperature": float(temperature), "top_k": int(top_k),
            "top_p": float(top_p), "seed": int(seed),
            "seeded": bool(seeded),
            "logit_bias": (
                {str(k): float(v) for k, v in logit_bias.items()}
                if logit_bias else None
            ),
        })
        return self._runner.insert(
            state, k, v, slot, true_len, first_token,
            temperature, top_k, top_p, seed, seeded, logit_bias,
        )

    def decode_step(self, state, key):
        self._leader.broadcast(
            {"op": "decode", "key": _key_data_list(key)}
        )
        return self._runner.decode_step(state, key)

    # -- chunked prefill (engine._advance_chunk) --------------------------
    # Chunk ops keep their own follower register so one-shot prefills
    # admitted BETWEEN chunks (the scheduling loop interleaves decode
    # and admission with chunk advancement) can't clobber the
    # in-progress job's accumulated K/V. Only device-free arguments ride
    # the wire — the follower's continuation consumes ITS OWN previous
    # chunk's arrays, which are bit-identical by replay determinism.

    def prefill_chunk(self, token_ids, true_len: int):
        self._leader.broadcast({
            "op": "chunk_start",
            "ids": [int(t) for t in token_ids],
            "true_len": int(true_len),
        })
        return self._runner.prefill(token_ids, true_len)

    def prefill_continue_chunk(
        self, k, v, start: int, token_ids, true_len: int,
        total_bucket: int,
    ):
        self._leader.broadcast({
            "op": "chunk_continue",
            "start": int(start),
            "ids": [int(t) for t in token_ids],
            "true_len": int(true_len),
            "total_bucket": int(total_bucket),
        })
        return self._runner.prefill_with_prefix(
            k, v, start, token_ids, true_len, total_bucket
        )

    def chunk_commit(self) -> None:
        """Completed chunk job: the follower promotes its chunk register
        to the insert register so the following sample_first/insert pair
        replays against the right arrays."""
        self._leader.broadcast({"op": "chunk_commit"})

    def chunk_abort(self) -> None:
        """Abandoned chunk job (client abort): followers drop their
        chunk register so the partial K/V doesn't stay pinned in HBM."""
        self._leader.broadcast({"op": "chunk_abort"})

    def deactivate(self, state, slot: int):
        self._leader.broadcast({"op": "deactivate", "slot": int(slot)})
        return self._runner.deactivate(state, slot)

    # -- single-host-only features (disabled at command build; defensive)

    def _unsupported(self, what: str):
        # ValueError: API handlers translate it to a clean 400 (e.g. an
        # embeddings request against a multi-host chat replica) instead
        # of a 500/loop-death
        raise ValueError(
            f"{what} is not supported on multi-host replicas "
            "(disabled at command build — worker/backends.py)"
        )

    def prefill_with_prefix(self, *a, **kw):
        self._unsupported("prefix-cache prefill")

    def prefill_with_embeds(self, *a, **kw):
        self._unsupported("vision-token prefill")

    def verify_step(self, *a, **kw):
        self._unsupported("speculative decoding")

    def ingest_step(self, *a, **kw):
        self._unsupported("draft ingestion")

    def embed(self, *a, **kw):
        self._unsupported("embeddings")


class FollowerLoop:
    """Follower side: replay the leader's op stream on the local runner.

    Runs in its own thread; the follower process's API server stays up
    for liveness but receives no inference traffic (the server proxies
    only to the leader's port)."""

    def __init__(
        self, runner, cmd_address: str, state,
        token: Optional[str] = None,
    ):
        self.runner = runner
        self.cmd_address = cmd_address
        self.token = channel_token() if token is None else token
        # REUSE the engine's already-created DecodeState: device_put over
        # a global mesh is a collective (it allgathers a shape/sharding
        # consistency check), so creating a second state here — a call
        # the leader never makes — would deadlock the whole replica at
        # startup. Leader and follower must perform identical sequences
        # of collective-bearing calls from process start.
        self.state = state
        self._reg: Optional[tuple] = None    # latest (last, k, v) prefill
        # in-progress chunked prefill's (last, k, v) — separate from
        # _reg so interleaved one-shot prefills can't clobber it
        self._chunk_reg: Optional[tuple] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.ops_applied = 0

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.run, name="mh-follower", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _connect(self) -> socket.socket:
        host, port = self.cmd_address.rsplit(":", 1)
        deadline = time.monotonic() + _CONNECT_TIMEOUT_S
        while True:
            try:
                sock = socket.create_connection((host, int(port)), 5.0)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sock.sendall(f"AUTH {self.token}\n".encode())
                # the 5s connect timeout must NOT persist into recv() —
                # an idle serving replica legitimately sends no commands
                # for long stretches; use a poll-sized timeout so the
                # loop can check _stop between reads
                sock.settimeout(2.0)
                return sock
            except OSError:
                if time.monotonic() > deadline or self._stop.is_set():
                    raise
                time.sleep(1.0)

    def run(self) -> None:
        sock = self._connect()
        logger.info("connected to leader command channel %s",
                    self.cmd_address)
        buf = b""
        try:
            while not self._stop.is_set():
                try:
                    chunk = sock.recv(1 << 16)
                except TimeoutError:
                    continue          # idle is normal; re-check _stop
                if not chunk:
                    logger.warning("leader command channel closed")
                    return
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if line.strip():
                        self._apply(json.loads(line))
        except OSError as e:
            logger.error("command channel error: %s", e)
        finally:
            sock.close()

    def _apply(self, op: Dict[str, Any]) -> None:
        kind = op["op"]
        r = self.runner
        def bias_of(op):
            raw = op.get("logit_bias")
            return (
                {int(k): float(v) for k, v in raw.items()} if raw else None
            )

        if kind == "prefill":
            self._reg = r.prefill(op["ids"], op["true_len"])
        elif kind == "chunk_start":
            self._chunk_reg = r.prefill(op["ids"], op["true_len"])
        elif kind == "chunk_continue":
            assert self._chunk_reg is not None, (
                "chunk_continue before chunk_start"
            )
            _, k, v = self._chunk_reg
            self._chunk_reg = r.prefill_with_prefix(
                k, v, op["start"], op["ids"], op["true_len"],
                op["total_bucket"],
            )
        elif kind == "chunk_commit":
            assert self._chunk_reg is not None, (
                "chunk_commit before chunk_start"
            )
            self._reg = self._chunk_reg
            self._chunk_reg = None
        elif kind == "chunk_abort":
            self._chunk_reg = None
        elif kind == "sample_first":
            assert self._reg is not None, "sample_first before prefill"
            r.sample_first(
                self._reg[0], op["temperature"], op["top_k"], op["top_p"],
                op["seed"], op["seeded"], op["position"],
                _key_from_list(op["key"]), bias_of(op),
            )
        elif kind == "insert":
            assert self._reg is not None, "insert before prefill"
            _, k, v = self._reg
            self.state = r.insert(
                self.state, k, v, op["slot"], op["true_len"],
                op["first_token"], op["temperature"], op["top_k"],
                op["top_p"], op["seed"], op["seeded"], bias_of(op),
            )
        elif kind == "decode":
            self.state, _ = r.decode_step(
                self.state, _key_from_list(op["key"])
            )
        elif kind == "deactivate":
            self.state = r.deactivate(self.state, op["slot"])
        else:
            logger.warning("unknown multihost op %r", kind)
            return
        self.ops_applied += 1
