"""Thin sheepd client + the ``sheep-submit`` CLI verb.

    from sheep_tpu.server.client import SheepClient

    with SheepClient("/run/sheepd.sock") as c:
        jid = c.submit("graph.bin64", k=64, tenant="alice")["job_id"]
        job = c.wait(jid, timeout_s=600)
        print(job["results"][0]["edge_cut"])

Addressing: a string containing ``/`` (or ending in ``.sock``) is a
unix socket path; ``host:port`` or a bare integer is TCP. One request
per call, synchronous. The client itself is sockets + json only — it
needs no accelerator and never touches a device (the parent package's
backend registry does import jax at interpreter load; the daemon-side
machinery proper — engine/scheduler — stays un-imported here, see
``sheep_tpu/server/__init__.py``).

CLI::

    sheep-submit --server /run/sheepd.sock --input g.edges --k 8,64 \\
        --wait [--output parts.pbin] [--tenant alice] [--deadline 60]
    sheep-submit --server ... --input g.edges --k 64 --watch
    sheep-submit --server ... --input g.edges --k 64 --resident --wait
    sheep-submit --server ... --update JOB --deltas g.dlog [--wire] \\
        [--score]
    sheep-submit --server ... --epoch-of JOB | --compact JOB
    sheep-submit --server ... --status JOB | --cancel JOB | --stats \\
        | --ping | --metrics | --profile DIR | --shutdown

Incremental verbs (ISSUE 15): ``--resident`` holds the finished
partition in the daemon; ``--update JOB --deltas LOG`` applies the
log's epochs past the resident epoch (daemon-side path by default;
``--wire`` reads the log here and streams each epoch inline — the
remote-tenant shape, idempotent via explicit epoch numbers);
``--epoch-of`` / ``--compact`` query and repair; ``--cancel`` on the
DONE job releases the residency. Also reachable as ``sheep update
JOB ...`` from the main CLI.

``--watch`` (ISSUE 11) submits and then POLLS ``status`` instead of
blocking in ``wait``: live progress lines on stderr (state, phase,
steps — the descriptor's per-job progress fields), final descriptor
JSON on stdout, same exit-code contract as ``--wait``. ``--metrics``
prints the daemon's Prometheus exposition text; ``--profile DIR``
(with ``--profile-steps K``) arms an on-demand jax.profiler capture
of the next K dispatch steps into daemon-side DIR.

Failover (ISSUE 14): ``SheepClient(..., reconnect=N)`` survives a
daemon bounce — transport errors reconnect with bounded exponential
backoff (``utils/retry.RetryPolicy`` machinery, transient class) and
re-send the request. Requests are only auto-retried when re-sending
is safe: everything except a plain ``submit`` (a blind resend could
double-build) and ``shutdown``; a submit WITH ``reattach=True`` is
idempotent (the daemon matches it to the journaled job by spec
digest) and therefore retried too. ``sheep-submit`` exposes this as
``--reconnect N``, defaulting ON for ``--watch`` so a daemon restart
mid-watch keeps the progress lines flowing instead of dying with a
connection error — the exit-code contract is unchanged.

Fleet mode (ISSUE 16): ``--endpoints a.sock,b.sock`` replaces
``--server`` with a comma list of replica addresses and routes the
submit through :class:`FleetClient` — a result-cache ``lookup`` of
the spec digest on every live replica first (a hit is answered with
zero build steps, so it short-circuits routing), then the replica
with the shallowest queue / largest admission headroom (scraped from
the live metrics gauges). A replica that dies while the job is being
waited on gets the job re-submitted — ``reattach``-idempotent — to
the next live replica; per-replica route counters land in the obs
trace as ``fleet_route`` events. Fleet mode covers the submit family
(``--wait`` / ``--watch`` included) and, since ISSUE 17, the
resident verbs: ``--update`` / ``--epoch-of`` / ``--compact`` route
to the replica OWNING the resident job (pinned after a status sweep)
and deliberately never fail over — resident state is replica-local.
Other admin verbs still address one replica via ``--server``.

Fleet observability (ISSUE 18): every submit mints a
W3C-traceparent-shaped trace context (``protocol.make_traceparent``)
sent as the request's ``trace`` field and re-sent on every later
wait/status/cancel/update naming that job; a FleetClient failover
resubmit REUSES the logical request's trace, so one trace id
correlates the client's ``fleet_request``/``fleet_failover`` spans
and every replica's job spans (``trace_report --stitch`` renders the
cross-process tree). The routing scrape is TTL-cached
(``SHEEP_FLEET_SCRAPE_TTL_S``, default 1 s) so submit bursts pay one
``/metrics`` round-trip per replica per window, with scrape wall cost
on the ``fleet_scrape_ms`` obs counter.

Chunked updates (ISSUE 17): :meth:`SheepClient.update` payloads too
large for the 1 MiB request line switch automatically to a
``begin`` / ``chunk`` / ``commit`` transaction over one connection,
applied by the daemon as ONE epoch at commit — a single call streams
an arbitrarily large epoch, and a client death mid-stream (no
commit) leaves the resident at its prior epoch, retryable from
scratch.

CLI (fleet)::

    sheep-submit --endpoints /run/a.sock,/run/b.sock \\
        --input g.edges --k 64 --wait

Exit codes: 0 op succeeded (for --wait/--watch: job DONE), 1 usage/
transport, 2 daemon answered ok=false, 3 job reached a non-done
terminal state (failed / cancelled / deadline_exceeded / rejected),
4 --wait's/--watch's --timeout elapsed with the job still queued/
running (not terminal — do not resubmit).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time
from typing import Optional

from sheep_tpu.server import protocol

# chunked-update slicing (ISSUE 17): 32768 edges base64-encode to
# ~700 KiB — comfortably under protocol.MAX_REQUEST_BYTES per line
UPDATE_CHUNK_EDGES = 32768


def _connect(server: str, timeout_s: float) -> socket.socket:
    server = str(server)
    if "/" in server or server.endswith(".sock"):
        s = socket.socket(socket.AF_UNIX)
        s.settimeout(timeout_s)
        s.connect(server)
        return s
    host, _, port = server.rpartition(":")
    try:
        port_n = int(port)
    except ValueError:
        raise ServerError(
            f"bad --server address {server!r}: want a unix socket path "
            f"(contains '/') or host:port") from None
    s = socket.create_connection((host or "127.0.0.1", port_n),
                                 timeout=timeout_s)
    return s


class SheepClient:
    """One connection to a sheepd; methods mirror the protocol ops and
    return the daemon's response body (raising :class:`ServerError`
    on ok=false). ``reconnect`` arms bounded transport failover (see
    module docstring); 0 keeps the classic fail-fast behavior."""

    def __init__(self, server: str, timeout_s: float = 600.0,
                 reconnect: int = 0, reconnect_base_s: float = 0.2):
        self.server = server
        self.timeout_s = timeout_s
        self.reconnect = int(reconnect)
        self._reconnect_base_s = float(reconnect_base_s)
        self._sock = None
        self._rf = None
        # job_id -> the traceparent minted at submit (ISSUE 18): every
        # later wait/status/cancel/update on that job re-sends the
        # SAME trace context, so the whole logical request correlates
        self._job_traces: dict = {}
        pol = self._policy()
        while True:
            try:
                self._open()
                return
            except OSError as e:
                # the restart window starts before the first connect:
                # a client launched while the daemon bounces should
                # wait for it, not die on ECONNREFUSED
                self._retry_or_raise(pol, e, "connect")

    def _policy(self):
        from sheep_tpu.utils import retry as retry_mod

        return retry_mod.RetryPolicy(max_retries=self.reconnect,
                                     base_delay_s=self._reconnect_base_s,
                                     max_delay_s=5.0)

    def _retry_or_raise(self, policy, exc, where: str) -> None:
        from sheep_tpu.utils import retry as retry_mod

        if policy is None or not policy.admit(retry_mod.TRANSIENT):
            raise exc
        policy.backoff(retry_mod.TRANSIENT, exc,
                       where=f"sheep-client.{where}")

    def _open(self) -> None:
        self._sock = _connect(self.server, self.timeout_s)
        self._rf = self._sock.makefile("rb")

    def _drop(self) -> None:
        try:
            if self._rf is not None:
                self._rf.close()
            if self._sock is not None:
                self._sock.close()
        except OSError:
            pass
        self._rf = None
        self._sock = None

    def close(self) -> None:
        self._drop()

    def __enter__(self) -> "SheepClient":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    @staticmethod
    def _retriable(doc: dict) -> bool:
        """Safe to blindly re-send after a transport error: everything
        except a plain submit (double-build risk — reattach makes it
        idempotent and thus retriable), an un-epoched update (a blind
        resend could double-fold; explicit epochs and the log form are
        idempotent — the daemon answers applied=false for an epoch it
        already holds), compact (double-compacting is observable), and
        shutdown."""
        op = doc.get("op")
        if op == "submit":
            return bool(doc.get("reattach"))
        if op == "update":
            if doc.get("stream") is not None:
                # chunked sub-verbs are transaction-scoped: resending
                # one on a FRESH connection can only hit "unknown
                # txn" — the whole-transaction retry in
                # _update_chunked owns recovery instead
                return False
            return doc.get("epoch") is not None \
                or doc.get("log") is not None
        return op not in ("shutdown", "compact")

    def request(self, doc: dict) -> dict:
        if "trace" not in doc:
            tp = self._job_traces.get(doc.get("job_id"))
            if tp is not None:
                doc = dict(doc, trace=tp)
        pol = self._policy() if self.reconnect > 0 \
            and self._retriable(doc) else None
        while True:
            try:
                if self._sock is None:
                    self._open()
                self._sock.sendall(protocol.dumps(doc))
                line = self._rf.readline()
                if not line:
                    raise ConnectionResetError(
                        "connection closed by daemon")
                resp = json.loads(line)
            except (OSError, json.JSONDecodeError) as e:
                self._drop()
                if isinstance(e, ConnectionResetError) and pol is None:
                    # the classic (reconnect=0) contract: a daemon
                    # that hangs up mid-request answers as a daemon
                    # error, not a transport one
                    raise ServerError(str(e)) from None
                self._retry_or_raise(pol, e,
                                     str(doc.get("op", "request")))
                continue
            if not resp.get("ok"):
                raise ServerError(resp.get("error",
                                           "unknown daemon error"))
            return resp

    # -- ops -----------------------------------------------------------
    def ping(self) -> dict:
        return self.request({"op": "ping"})

    def _mint_trace(self) -> str:
        """One fresh wire trace context per logical request (ISSUE
        18), parented to the calling thread's current obs span when
        one is open — the daemon's job span then stitches under it
        (``trace_report --stitch``)."""
        from sheep_tpu import obs

        return protocol.make_traceparent(protocol.mint_trace_id(),
                                         obs.current_span_id())

    def submit(self, input: str, k, tenant: str = "default",
               reattach: bool = False, trace: Optional[str] = None,
               **job_fields) -> dict:
        """``reattach=True`` makes the submit idempotent: the daemon
        matches the spec digest against existing jobs (journaled ones
        included) and returns the live/completed twin — with
        ``"reattached": true`` in the response — instead of building
        again. The safe shape for retried submits across a daemon
        restart.

        ``trace`` overrides the wire trace context (a FleetClient
        failover resubmit reuses the logical request's); by default a
        fresh one is minted per submit and re-sent on every later
        request naming the returned job id."""
        job = {"input": input, "k": k, **job_fields}
        req = {"op": "submit", "tenant": tenant, "job": job,
               "trace": trace or self._mint_trace()}
        if reattach:
            req["reattach"] = True
        resp = self.request(req)
        jid = resp.get("job_id")
        if jid:
            self._job_traces[jid] = req["trace"]
        return resp

    def status(self, job_id: str) -> dict:
        return self.request({"op": "status", "job_id": job_id})["job"]

    def wait(self, job_id: str,
             timeout_s: Optional[float] = None) -> dict:
        return self.request({"op": "wait", "job_id": job_id,
                             "timeout_s": timeout_s})["job"]

    def cancel(self, job_id: str) -> str:
        return self.request({"op": "cancel",
                             "job_id": job_id})["state"]

    def list(self) -> list:
        return self.request({"op": "list"})["jobs"]

    def stats(self) -> dict:
        return self.request({"op": "stats"})["stats"]

    def metrics(self) -> str:
        """The daemon's live Prometheus exposition text (same document
        as HTTP GET /metrics on --metrics-port)."""
        return self.request({"op": "metrics"})["text"]

    def lookup(self, digest: str) -> bool:
        """Advisory result-cache probe (ISSUE 16): True when the
        daemon can answer a submit with this spec digest straight
        from its result store — zero build steps, zero compiles. See
        :func:`fleet_digest` for computing the digest client-side."""
        return bool(self.request({"op": "lookup",
                                  "digest": digest})["hit"])

    # -- resident-partition verbs (ISSUE 15) ---------------------------
    def update(self, job_id: str, adds=None, dels=None,
               epoch: Optional[int] = None, score: bool = False,
               compact: str = "auto", log: Optional[str] = None,
               chunk_edges: Optional[int] = None) -> dict:
        """Stream one delta epoch at a resident partition: ``adds`` /
        ``dels`` are (m, 2) edge arrays (base64 on the wire), or
        ``log`` names a DAEMON-side delta log whose epochs past the
        resident epoch all apply. Explicit ``epoch`` numbers make the
        call idempotent (an already-applied epoch answers
        ``applied: false``).

        Payloads too large for the 1 MiB request line switch to the
        chunked wire form automatically (ISSUE 17): one begin /
        chunk* / commit transaction over this connection, applied by
        the daemon as ONE epoch at commit — so a single call streams
        an arbitrarily large epoch. ``chunk_edges`` overrides the
        per-chunk edge count (default ``UPDATE_CHUNK_EDGES``)."""
        ce = int(chunk_edges) if chunk_edges else UPDATE_CHUNK_EDGES
        n = (0 if adds is None else len(adds)) \
            + (0 if dels is None else len(dels))
        if log is None and n > ce:
            return self._update_chunked(job_id, adds, dels, epoch,
                                        score, compact, ce)
        req = {"op": "update", "job_id": job_id,
               "score": bool(score), "compact": compact}
        if adds is not None:
            req["adds"] = protocol.encode_edges(adds)
        if dels is not None:
            req["dels"] = protocol.encode_edges(dels)
        if epoch is not None:
            req["epoch"] = int(epoch)
        if log is not None:
            req["log"] = log
        return self.request(req)

    def _update_chunked(self, job_id: str, adds, dels, epoch,
                        score: bool, compact: str,
                        chunk_edges: int) -> dict:
        """One chunked update transaction. Retries (when armed AND the
        epoch is explicit, i.e. idempotent) restart from ``begin``:
        transactions are connection-scoped, so a transport drop
        anywhere mid-stream discards the staged chunks server-side
        and the only safe resume point is a fresh transaction."""
        pol = self._policy() if self.reconnect > 0 \
            and epoch is not None else None
        while True:
            try:
                txn = self.request({"op": "update", "job_id": job_id,
                                    "stream": "begin"})["txn"]
                for key, arr in (("adds", adds), ("dels", dels)):
                    if arr is None:
                        continue
                    for lo in range(0, len(arr), chunk_edges):
                        part = arr[lo:lo + chunk_edges]
                        self.request({
                            "op": "update", "stream": "chunk",
                            "txn": txn,
                            key: protocol.encode_edges(part)})
                commit = {"op": "update", "stream": "commit",
                          "txn": txn, "score": bool(score),
                          "compact": compact}
                if epoch is not None:
                    commit["epoch"] = int(epoch)
                return self.request(commit)
            except (OSError, ServerError) as e:
                if isinstance(e, ServerError) \
                        and "connection closed" not in str(e) \
                        and "unknown update txn" not in str(e):
                    raise  # a real daemon answer, not a torn stream
                if pol is None:
                    raise
                self._drop()
                self._retry_or_raise(pol, e, "update.stream")

    def epoch(self, job_id: str) -> dict:
        """Resident-partition epoch/staleness descriptor."""
        return self.request({"op": "epoch", "job_id": job_id})

    def compact(self, job_id: str, mode: str = "auto",
                score: bool = False) -> dict:
        """Run tombstone compaction on a resident partition."""
        return self.request({"op": "compact", "job_id": job_id,
                             "mode": mode, "score": bool(score)})

    def profile(self, dir: str, steps: int = 8) -> dict:
        """Arm an on-demand jax.profiler capture of the next ``steps``
        dispatch steps into daemon-side directory ``dir``; completion
        is queryable via :meth:`stats`'s ``profile`` field."""
        return self.request({"op": "profile", "dir": dir,
                             "steps": steps})["profile"]

    def shutdown(self, drain: bool = False) -> dict:
        return self.request({"op": "shutdown", "drain": drain})

    def result_assignment(self, job: dict, k: Optional[int] = None):
        """Decode the packed assignment for part count ``k`` (default:
        the job's first) from a wait/status descriptor — only present
        when the job was submitted with ``return_assignment``."""
        for row in job.get("results") or []:
            if k is None or row.get("k") == k:
                if "assignment" not in row:
                    break
                return protocol.decode_assignment(row["assignment"])
        raise ServerError(
            f"job {job.get('job_id')} carries no assignment for k={k} "
            f"(submit with return_assignment=true)")


class ServerError(RuntimeError):
    """The daemon answered ok=false (or went away mid-request)."""


def fleet_digest(input: str, k, tenant: str = "default",
                 **job_fields) -> str:
    """The spec digest a daemon would journal for this submit,
    computed CLIENT-side through the same ``JobSpec.from_request`` +
    ``journal.job_digest`` pair the daemon runs (the digest folds in
    the input file's size/mtime via os.stat, so it matches when
    client and daemons see the same filesystem — the unix-socket
    fleet shape). This is the result-cache / reattach key: any
    replica holding it answers the submit without building."""
    from sheep_tpu.server import journal as journal_mod

    job = {"input": input, "k": k, **job_fields}
    spec = protocol.JobSpec.from_request(job, tenant=tenant)
    return journal_mod.job_digest(spec)


def _trace_id_of(traceparent: Optional[str]) -> Optional[str]:
    """The bare 32-hex trace id out of a wire traceparent (None when
    absent/malformed) — what grep-able obs events carry."""
    if not traceparent:
        return None
    try:
        return protocol.parse_traceparent(traceparent)[0]
    except protocol.ProtocolError:
        return None


class FleetClient:
    """Routes submits across a fleet of sheepd replicas (ISSUE 16).

    Per submit, in order:

    1. digest short-circuit — every live replica answers ``lookup``
       for the spec digest; a result-cache hit routes the submit
       straight there (it completes with zero build steps);
    2. headroom routing — otherwise the submit goes to the replica
       with the least load, ordered by queued+active jobs then by
       largest admission headroom, both scraped from the live
       metrics gauges (``sheepd_queue_depth`` +
       ``sheepd_active_jobs``, ``sheepd_headroom_bytes``);
    3. failover — a replica that dies while one of its jobs is being
       waited on (or status-polled) gets that job re-submitted to
       the next live replica. Failover resubmits carry
       ``reattach=True`` (a bounced-but-journaled daemon reattaches
       instead of double-building); FIRST submits are plain, so a
       repeat request reaches the result store instead of
       reattaching to a retained terminal twin.

    ``route_counts`` tallies submits per endpoint; every routing
    decision also lands in the obs trace as a ``fleet_route`` event
    with the running counters. ``reconnect`` is the per-endpoint
    transport retry budget (as :class:`SheepClient`); the default 0
    fails fast into the failover path, which is usually what a fleet
    wants — a *dead* replica should not be backed off against when a
    live one can take the job.
    """

    def __init__(self, endpoints, timeout_s: float = 600.0,
                 reconnect: int = 0, reconnect_base_s: float = 0.2):
        if isinstance(endpoints, str):
            endpoints = endpoints.split(",")
        eps = [str(e).strip() for e in endpoints if str(e).strip()]
        if not eps:
            raise ValueError("FleetClient needs at least one endpoint")
        self.endpoints = eps
        self.timeout_s = float(timeout_s)
        self.reconnect = int(reconnect)
        self._reconnect_base_s = float(reconnect_base_s)
        self._clients: dict = {}
        self.route_counts = {ep: 0 for ep in eps}
        # (endpoint, job_id) -> (input, k, tenant, job_fields, trace)
        # — what failover needs to re-place the job on a surviving
        # replica (the trace context is REUSED: a failover resubmit is
        # the same logical request, ISSUE 18). Keyed by BOTH because
        # daemon job ids are per-process counters: two replicas
        # routinely mint the same "j1".
        self._jobs: dict = {}
        # routing-scrape TTL cache (ISSUE 18): a burst of submits
        # within the TTL reuses one /metrics round-trip per replica
        # instead of paying N; load keys go stale by at most the TTL,
        # which headroom routing tolerates (admission re-checks)
        try:
            self.scrape_ttl_s = float(
                os.environ.get("SHEEP_FLEET_SCRAPE_TTL_S", "1.0"))
        except ValueError:
            self.scrape_ttl_s = 1.0
        self._load_cache: dict = {}  # ep -> (monotonic ts, load key)
        # job_id -> endpoint pins for the resident verbs (ISSUE 17):
        # resident state is replica-local, so update/epoch/compact
        # must keep hitting the owning replica and NEVER fail over
        self._resident: dict = {}

    def close(self) -> None:
        for c in self._clients.values():
            c.close()
        self._clients.clear()

    def __enter__(self) -> "FleetClient":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def _client(self, ep: str) -> SheepClient:
        c = self._clients.get(ep)
        if c is None:
            c = SheepClient(ep, timeout_s=self.timeout_s,
                            reconnect=self.reconnect,
                            reconnect_base_s=self._reconnect_base_s)
            self._clients[ep] = c
        return c

    def _down(self, ep: str) -> bool:
        """Distinguish a dead replica from a daemon that answered an
        error: a live one still pings."""
        try:
            self._client(ep).ping()
            return False
        except (ServerError, OSError, json.JSONDecodeError):
            return True

    def _lookup_round(self, digest: str):
        """One lookup sweep: (live_endpoints, first_hit_endpoint)."""
        live, hit = [], None
        for ep in self.endpoints:
            try:
                r = self._client(ep).request({"op": "lookup",
                                              "digest": digest})
                live.append(ep)
                if hit is None and r.get("hit"):
                    hit = ep
            except ServerError:
                # the daemon answered (maybe a pre-fleet one without
                # the lookup verb): live, treated as a miss
                live.append(ep)
            except (OSError, json.JSONDecodeError):
                pass
        return live, hit

    def _load(self, ep: str):
        """(queued+active, -headroom) load key; None if unreachable.
        Answers from the TTL cache within ``scrape_ttl_s`` of the last
        scrape (ISSUE 18); each real scrape's wall cost lands on the
        ``fleet_scrape_ms`` obs counter, cache answers on
        ``fleet_scrape_cache_hits``."""
        from sheep_tpu import obs

        cached = self._load_cache.get(ep)
        if cached is not None \
                and time.monotonic() - cached[0] < self.scrape_ttl_s:
            obs.inc("fleet_scrape_cache_hits")
            return cached[1]
        t0 = time.perf_counter()
        try:
            text = self._client(ep).metrics()
        except (ServerError, OSError, json.JSONDecodeError):
            self._load_cache[ep] = (time.monotonic(), None)
            return None
        obs.inc("fleet_scrape_ms",
                round((time.perf_counter() - t0) * 1000.0, 3))
        from sheep_tpu.obs.metrics import parse_prometheus

        gauges = parse_prometheus(text)

        def one(name, default):
            rows = gauges.get(name) or []
            return float(rows[0][1]) if rows else default

        depth = one("sheepd_queue_depth", 0.0) \
            + one("sheepd_active_jobs", 0.0)
        headroom = one("sheepd_headroom_bytes", float("inf"))
        key = (depth, -headroom)
        self._load_cache[ep] = (time.monotonic(), key)
        return key

    def _route(self, live):
        scored = []
        for i, ep in enumerate(live):
            load = self._load(ep)
            if load is not None:
                scored.append((load, i, ep))
        if not scored:
            return live[0] if live else None
        scored.sort()
        return scored[0][2]

    def _submit_to(self, ep: str, why: str, digest: str, input: str,
                   k, tenant: str, job_fields: dict,
                   reattach: bool = False,
                   trace: Optional[str] = None) -> dict:
        from sheep_tpu import obs

        resp = self._client(ep).submit(input, k=k, tenant=tenant,
                                       reattach=reattach, trace=trace,
                                       **job_fields)
        self.route_counts[ep] = self.route_counts.get(ep, 0) + 1
        jid = resp.get("job_id")
        if jid:
            self._jobs[(ep, jid)] = (input, k, tenant,
                                     dict(job_fields), trace)
        obs.event("fleet_route", endpoint=ep, why=why, digest=digest,
                  job_id=jid, trace=_trace_id_of(trace),
                  counts=dict(self.route_counts))
        resp["endpoint"] = ep
        return resp

    def submit(self, input: str, k, tenant: str = "default",
               reattach: bool = False, **job_fields) -> dict:
        """Route one submit per the class policy. ``reattach`` is
        accepted for :class:`SheepClient` signature compatibility but
        ignored: first submits are plain (a repeat digest must reach
        the result store, not reattach to a retained terminal twin);
        failover resubmission adds ``reattach=True`` itself.

        One trace id is minted per LOGICAL request (ISSUE 18): the
        client-side ``fleet_request`` span carries it, the wire
        ``trace`` field propagates it to whichever replica takes the
        job, and a later failover resubmit reuses it — so the client
        route span and every replica's job span stitch into one tree
        (``trace_report --stitch``)."""
        del reattach
        from sheep_tpu import obs

        digest = fleet_digest(input, k, tenant=tenant, **job_fields)
        tid = protocol.mint_trace_id()
        sp = obs.begin_detached("fleet_request", trace=tid,
                                digest=digest, tenant=str(tenant))
        tp = protocol.make_traceparent(tid, getattr(sp, "id", None))
        tried: set = set()
        try:
            while True:
                live, hit = self._lookup_round(digest)
                live = [e for e in live if e not in tried]
                if hit is not None and hit not in tried:
                    ep, why = hit, "cache_hit"
                else:
                    ep, why = self._route(live), "headroom"
                if ep is None:
                    raise ServerError("no live endpoint among "
                                      + ",".join(self.endpoints))
                try:
                    resp = self._submit_to(ep, why, digest, input, k,
                                           tenant, dict(job_fields),
                                           trace=tp)
                    sp.annotate(endpoint=ep, why=why,
                                job_id=resp.get("job_id"))
                    return resp
                except (OSError, json.JSONDecodeError):
                    # died between lookup and submit: strike, reroute
                    tried.add(ep)
        finally:
            sp.end()

    def _resolve(self, job):
        """(endpoint, job_id) key for a job handle.

        The handle is either a submit/status DESCRIPTOR (preferred —
        its ``endpoint`` + ``job_id`` pin the replica) or a bare job
        id, honored only while unambiguous: daemon job ids are
        per-process counters, so two replicas routinely mint the same
        ``j1``, and guessing between them could answer a wait with a
        DIFFERENT tenant's job."""
        if isinstance(job, dict):
            ep, jid = job.get("endpoint"), job.get("job_id")
            if ep is not None and (ep, jid) in self._jobs:
                return ep, jid
            job = jid
        matches = [key for key in self._jobs if key[1] == job]
        if len(matches) == 1:
            return matches[0]
        if not matches:
            raise ServerError(f"unknown fleet job {job}")
        raise ServerError(
            f"job id {job} is ambiguous across replicas "
            f"({', '.join(ep for ep, _ in matches)}) — pass the "
            f"submit descriptor (it carries the endpoint) instead "
            f"of the bare id")

    def _failover(self, key, exc) -> dict:
        """The job's home replica is gone: re-place it on a survivor
        (reattach-idempotent) and return the NEW descriptor. The
        resubmit REUSES the logical request's trace context, and the
        client-side ``fleet_failover`` span nests under the original
        ``fleet_request`` span — the failover seam is one visible
        edge in the stitched tree (ISSUE 18)."""
        from sheep_tpu import obs

        home, job_id = key
        sub = self._jobs.get(key)
        if sub is None:
            raise exc
        self._jobs.pop(key, None)
        input, k, tenant, job_fields, tp = sub
        digest = fleet_digest(input, k, tenant=tenant, **job_fields)
        tid = parent = None
        if tp:
            try:
                tid, phex = protocol.parse_traceparent(tp)
                parent = int(phex, 16) if phex else None
            except protocol.ProtocolError:
                pass
        sp = obs.begin_detached("fleet_failover", parent=parent,
                                trace=tid, from_endpoint=home,
                                from_job=job_id)
        try:
            for ep in self.endpoints:
                if ep == home or self._down(ep):
                    continue
                try:
                    resp = self._submit_to(ep, "failover", digest,
                                           input, k, tenant,
                                           job_fields, reattach=True,
                                           trace=tp)
                    sp.annotate(endpoint=ep,
                                job_id=resp.get("job_id"))
                    return resp
                except (ServerError, OSError, json.JSONDecodeError):
                    continue
            raise ServerError(
                f"job {job_id}: home replica {home} died and no live "
                f"replica accepted the failover resubmit") from exc
        finally:
            sp.end()

    def status(self, job) -> dict:
        """Job descriptor, following failover: when the home replica
        died the job is re-placed and the returned descriptor carries
        the NEW job_id/endpoint — poll loops should track the
        descriptor, not the bare id."""
        while True:
            ep, jid = self._resolve(job)
            try:
                return self._client(ep).status(jid)
            except (ServerError, OSError,
                    json.JSONDecodeError) as e:
                if isinstance(e, ServerError) and not self._down(ep):
                    raise
                job = self._failover((ep, jid), e)

    def wait(self, job, timeout_s: Optional[float] = None) -> dict:
        """Block until terminal, following failover like
        :meth:`status` (the returned descriptor is authoritative)."""
        while True:
            ep, jid = self._resolve(job)
            try:
                return self._client(ep).wait(jid, timeout_s)
            except (ServerError, OSError,
                    json.JSONDecodeError) as e:
                if isinstance(e, ServerError) and not self._down(ep):
                    raise
                job = self._failover((ep, jid), e)

    def result_assignment(self, job: dict, k: Optional[int] = None):
        return SheepClient.result_assignment(self, job, k)

    # -- resident-partition verbs across the fleet (ISSUE 17) ----------
    def _locate_resident(self, job) -> "tuple":
        """Pin the replica owning a resident job.

        The handle is a submit descriptor (its ``endpoint`` pins
        directly) or a bare id, resolved by sweeping every replica's
        ``status`` — exactly one owner pins it, zero or several is an
        error. Unlike the submit family these verbs NEVER fail over:
        the resident table lives in the owning replica's memory and
        state dir, so another replica cannot answer for it."""
        if isinstance(job, dict):
            ep, jid = job.get("endpoint"), job.get("job_id")
            if ep is not None and jid is not None:
                self._resident[jid] = ep
                return ep, jid
            job = jid
        job_id = str(job)
        ep = self._resident.get(job_id)
        if ep is not None:
            return ep, job_id
        owners = []
        for cand in self.endpoints:
            try:
                self._client(cand).status(job_id)
                owners.append(cand)
            except ServerError:
                continue  # live replica, doesn't know the job
            except (OSError, json.JSONDecodeError):
                continue  # dead replica: nothing servable there
        if not owners:
            raise ServerError(
                f"no live replica knows job {job_id!r} (swept "
                f"{','.join(self.endpoints)}); resident partitions "
                f"are replica-local — if the owning replica died, "
                f"restart it (durable daemons resume residents) or "
                f"resubmit --resident elsewhere")
        if len(owners) > 1:
            raise ServerError(
                f"job id {job_id!r} is ambiguous across replicas "
                f"({', '.join(owners)}) — daemon job ids are "
                f"per-process counters; pass the submit descriptor "
                f"(it carries the endpoint) instead of the bare id")
        self._resident[job_id] = owners[0]
        return owners[0], job_id

    def _resident_call(self, job, fn):
        ep, job_id = self._locate_resident(job)
        try:
            return fn(self._client(ep), job_id)
        except (OSError, json.JSONDecodeError) as e:
            self._resident.pop(job_id, None)
            raise ServerError(
                f"replica {ep} owning resident job {job_id} went "
                f"away mid-request ({e}); resident state is "
                f"replica-local so this verb cannot fail over — "
                f"restart that replica (a durable daemon resumes its "
                f"resident partitions at their last epoch) and "
                f"retry") from e

    def update(self, job, adds=None, dels=None,
               epoch: Optional[int] = None, score: bool = False,
               compact: str = "auto", log: Optional[str] = None,
               chunk_edges: Optional[int] = None) -> dict:
        """Apply a delta epoch to a resident job's OWNING replica
        (pinned; see :meth:`_locate_resident`). Signature and chunked
        streaming as :meth:`SheepClient.update`."""
        return self._resident_call(
            job, lambda c, jid: c.update(
                jid, adds=adds, dels=dels, epoch=epoch, score=score,
                compact=compact, log=log, chunk_edges=chunk_edges))

    def epoch(self, job) -> dict:
        return self._resident_call(
            job, lambda c, jid: c.epoch(jid))

    def compact(self, job, mode: str = "auto",
                score: bool = False) -> dict:
        return self._resident_call(
            job, lambda c, jid: c.compact(jid, mode=mode,
                                          score=score))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sheep-submit",
        description="submit partition jobs to a running sheepd")
    p.add_argument("--server",
                   help="daemon address: unix socket path or host:port")
    p.add_argument("--endpoints", metavar="A,B,...", default=None,
                   help="fleet mode: comma list of replica addresses; "
                        "submits route to a result-cache digest hit "
                        "first, else the least-loaded replica, with "
                        "failover resubmission if a replica dies. "
                        "Resident verbs (--update/--epoch-of/"
                        "--compact) route to the replica OWNING the "
                        "job and never fail over; other admin verbs "
                        "use --server")
    p.add_argument("--input", help="graph path or synthetic spec "
                                   "(as the main CLI's --input)")
    p.add_argument("--k", help="part count, or comma list for multi-k "
                               "from one shared tree")
    p.add_argument("--tenant", default="default")
    p.add_argument("--chunk-edges", type=int, default=None)
    p.add_argument("--dispatch-batch", type=int, default=None)
    p.add_argument("--h2d-ring", type=int, default=None,
                   help="staged H2D ring depth for host-format inputs "
                        "(0 = auto; device-generated specs skip "
                        "staging)")
    p.add_argument("--inflight", type=int, default=None,
                   help="in-job dispatch pipeline depth: confirmed "
                        "executions in flight per engine step "
                        "(default 1)")
    p.add_argument("--alpha", type=float, default=None)
    p.add_argument("--weights", choices=["unit", "degree"], default=None)
    p.add_argument("--comm-volume", action="store_true")
    p.add_argument("--num-vertices", type=int, default=None)
    p.add_argument("--deadline", type=float, default=None, metavar="S",
                   help="seconds from submit until the job must be "
                        "done (expired -> deadline_exceeded)")
    p.add_argument("--output", default=None,
                   help="daemon-side partition map path (.parts/.pbin)")
    p.add_argument("--wait", action="store_true",
                   help="block until the job is terminal; print its "
                        "descriptor; exit 0 only on done")
    p.add_argument("--watch", action="store_true",
                   help="like --wait but poll status and render live "
                        "progress lines (state/phase/steps) on stderr "
                        "instead of blocking silently")
    p.add_argument("--poll", type=float, default=0.5, metavar="S",
                   help="with --watch: poll interval (default 0.5s)")
    p.add_argument("--reconnect", type=int, default=None, metavar="N",
                   help="survive a daemon bounce: retry transport "
                        "errors up to N times with exponential "
                        "backoff, re-sending idempotent requests "
                        "(submits reattach to the journaled job by "
                        "digest instead of double-building). Default: "
                        "8 with --watch, else 0")
    p.add_argument("--timeout", type=float, default=None,
                   help="with --wait/--watch: give up after this many "
                        "seconds")
    p.add_argument("--resident", action="store_true",
                   help="with --input: hold the finished partition "
                        "RESIDENT in the daemon so delta epochs can "
                        "stream at it (--update); the admission "
                        "reservation stays charged until --cancel "
                        "releases it")
    p.add_argument("--update", metavar="JOB", default=None,
                   help="apply delta epochs to a resident partition; "
                        "needs --deltas LOG (daemon-side path by "
                        "default, --wire streams each epoch inline)")
    p.add_argument("--deltas", metavar="LOG", default=None,
                   help="with --update: the delta log "
                        "(io/deltalog.py) whose epochs past the "
                        "resident epoch apply")
    p.add_argument("--wire", action="store_true",
                   help="with --update: read the log CLIENT-side and "
                        "stream each epoch as an inline update "
                        "request (the remote-tenant path; default "
                        "sends the daemon-side log path)")
    p.add_argument("--score", action="store_true",
                   help="with --update/--compact: refresh + return "
                        "the scored results after applying")
    p.add_argument("--epoch-of", metavar="JOB", default=None,
                   help="print a resident partition's epoch/staleness "
                        "descriptor")
    p.add_argument("--compact", metavar="JOB", default=None,
                   help="compact a resident partition's tombstones")
    p.add_argument("--compact-mode", default="auto",
                   choices=["auto", "full", "subtree", "rebase"],
                   help="with --compact: full re-anchors and rebuilds "
                        "everything (exact), subtree repairs only the "
                        "dirty tree-split parts (score-bounded), "
                        "rebase additionally rewrites base+deltas "
                        "into a fresh on-disk artifact (durable "
                        "daemons only; explicit opt-in), auto picks "
                        "between full/subtree (default)")
    p.add_argument("--status", metavar="JOB")
    p.add_argument("--cancel", metavar="JOB")
    p.add_argument("--stats", action="store_true")
    p.add_argument("--ping", action="store_true")
    p.add_argument("--metrics", action="store_true",
                   help="print the daemon's live Prometheus text")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="arm an on-demand jax.profiler capture into "
                        "daemon-side DIR")
    p.add_argument("--profile-steps", type=int, default=8, metavar="K",
                   help="with --profile: capture the next K dispatch "
                        "steps (default 8)")
    p.add_argument("--shutdown", action="store_true")
    p.add_argument("--drain", action="store_true",
                   help="with --shutdown: finish accepted jobs first")
    return p


def _watch_job(c: "SheepClient", job, poll_s: float,
               timeout_s: Optional[float]) -> dict:
    """Poll status until terminal (or timeout), rendering one progress
    line per change on stderr; returns the last descriptor. ``job``
    is a bare id (SheepClient) or the submit descriptor (FleetClient
    — replica job ids collide, the descriptor pins the endpoint).
    Daemon bounces are absorbed below in ``request`` when the client
    was built with ``reconnect`` (the --watch default): each poll
    retries transports with backoff, so a restarting daemon shows up
    as a few stderr retry notes and then the resumed job's progress —
    not a dead watch."""
    t0 = time.monotonic()
    deadline = None if timeout_s is None else t0 + timeout_s
    last_line = None
    while True:
        desc = c.status(job)
        # fleet failover re-places a job on a surviving replica under
        # a NEW id; the descriptor's job_id is authoritative
        job = desc.get("job_id") or job
        job_id = job if isinstance(job, str) else job.get("job_id")
        state = desc.get("state")
        bits = [f"{time.monotonic() - t0:7.1f}s", job_id, state]
        if desc.get("phase"):
            bits.append(f"phase={desc['phase']}")
        if desc.get("steps"):
            bits.append(f"steps={desc['steps']}")
        if state == "done" and desc.get("results"):
            r = desc["results"][0]
            bits.append(f"cut_ratio={r.get('cut_ratio')}")
        if desc.get("error"):
            bits.append(f"error={desc['error'][:120]}")
        line = " ".join(bits)
        if line != last_line:
            print(f"sheep-submit: {line}", file=sys.stderr, flush=True)
            last_line = line
        if state in protocol.TERMINAL_STATES:
            return desc
        if deadline is not None and time.monotonic() >= deadline:
            return desc
        time.sleep(max(0.05, poll_s))


def main(argv=None) -> int:
    p = build_parser()
    args = p.parse_args(argv)
    modes = [bool(args.input), bool(args.status), bool(args.cancel),
             args.stats, args.ping, args.shutdown, args.metrics,
             bool(args.profile), bool(args.update),
             bool(args.epoch_of), bool(args.compact)]
    if sum(modes) != 1:
        p.error("pass exactly one of --input (submit), --status, "
                "--cancel, --stats, --ping, --metrics, --profile, "
                "--update, --epoch-of, --compact, --shutdown")
    if bool(args.server) == bool(args.endpoints):
        p.error("pass exactly one of --server or --endpoints")
    if args.endpoints and not (args.input or args.update
                               or args.epoch_of or args.compact):
        p.error("--endpoints (fleet mode) covers submits and the "
                "resident verbs (--update/--epoch-of/--compact, "
                "routed to the replica owning the job); point "
                "--server at one replica for other admin verbs")
    if args.update and not args.deltas:
        p.error("--update needs --deltas LOG")
    reconnect = args.reconnect if args.reconnect is not None \
        else (8 if args.watch else 0)
    if reconnect < 0:
        p.error("--reconnect must be >= 0")
    try:
        if args.endpoints:
            client = FleetClient(args.endpoints, reconnect=reconnect)
        else:
            client = SheepClient(args.server, reconnect=reconnect)
        with client as c:
            if args.ping:
                print(json.dumps(c.ping()))
                return 0
            if args.stats:
                print(json.dumps(c.stats(), indent=1))
                return 0
            if args.metrics:
                sys.stdout.write(c.metrics())
                return 0
            if args.profile:
                print(json.dumps(c.profile(args.profile,
                                           steps=args.profile_steps)))
                return 0
            if args.shutdown:
                print(json.dumps(c.shutdown(drain=args.drain)))
                return 0
            if args.epoch_of:
                print(json.dumps(c.epoch(args.epoch_of)))
                return 0
            if args.compact:
                print(json.dumps(c.compact(args.compact,
                                           mode=args.compact_mode,
                                           score=args.score)))
                return 0
            if args.update:
                if args.wire:
                    # remote-tenant path: read the log HERE, stream
                    # each epoch inline (idempotent: explicit epoch
                    # numbers — an already-applied epoch is a no-op)
                    from sheep_tpu.io.deltalog import DeltaLogReader

                    cur = int(c.epoch(args.update)["epoch"])
                    resp = {"job_id": args.update, "epoch": cur,
                            "applied": False, "epochs_applied": 0}
                    applied = 0
                    reader = DeltaLogReader(args.deltas)
                    mx = reader.max_epoch  # records() cached: 1 read
                    for ep, adds, dels in reader.epochs(
                            start_epoch=cur):
                        resp = c.update(args.update, adds=adds,
                                        dels=dels, epoch=ep,
                                        score=args.score and ep == mx)
                        applied += resp.get("epochs_applied", 0)
                    resp["epochs_applied"] = applied
                    resp["applied"] = applied > 0
                else:
                    resp = c.update(args.update, log=args.deltas,
                                    score=args.score)
                print(json.dumps(resp))
                return 0
            if args.status:
                print(json.dumps(c.status(args.status)))
                return 0
            if args.cancel:
                print(json.dumps({"job_id": args.cancel,
                                  "state": c.cancel(args.cancel)}))
                return 0
            # submit
            if not args.k:
                p.error("--input needs --k")
            try:
                ks = [int(x) for x in str(args.k).split(",") if x != ""]
            except ValueError:
                ks = []
            if not ks or any(k < 1 for k in ks):
                p.error(f"--k must be a positive int or comma list "
                        f"(got {args.k!r})")
            job = {"k": ks}
            for field, val in (("chunk_edges", args.chunk_edges),
                               ("dispatch_batch", args.dispatch_batch),
                               ("h2d_ring", args.h2d_ring),
                               ("inflight", args.inflight),
                               ("alpha", args.alpha),
                               ("weights", args.weights),
                               ("num_vertices", args.num_vertices),
                               ("deadline_s", args.deadline),
                               ("output", args.output)):
                if val is not None:
                    job[field] = val
            if args.comm_volume:
                job["comm_volume"] = True
            if args.resident:
                job["resident"] = True
            # with failover armed the submit itself must be idempotent
            # (the retried submit against a restarted daemon reattaches
            # to the journaled job instead of double-building)
            resp = c.submit(args.input, tenant=args.tenant,
                            reattach=reconnect > 0, **job)
            if not (args.wait or args.watch):
                print(json.dumps(resp))
                return 0
            # fleet handles are the full descriptor (replica job ids
            # collide across daemons; the endpoint disambiguates)
            handle = resp if args.endpoints else resp["job_id"]
            if args.watch:
                desc = _watch_job(c, handle, args.poll, args.timeout)
            else:
                desc = c.wait(handle, timeout_s=args.timeout)
            print(json.dumps(desc))
            if desc.get("state") == "done":
                return 0
            if desc.get("state") in ("queued", "running"):
                # --timeout elapsed with the job still in flight: NOT a
                # terminal failure — a supervisor must not resubmit
                print(f"sheep-submit: wait timed out; job "
                      f"{desc.get('job_id')} is still "
                      f"{desc.get('state')}", file=sys.stderr)
                return 4
            return 3
    except (ServerError, OSError, json.JSONDecodeError) as e:
        kind = "daemon" if isinstance(e, ServerError) else "transport"
        print(f"sheep-submit: {kind} error: {e}", file=sys.stderr)
        return 2 if isinstance(e, ServerError) else 1


if __name__ == "__main__":
    sys.exit(main())
