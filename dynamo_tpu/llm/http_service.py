"""OpenAI-compatible HTTP frontend (aiohttp).

Routes: POST /v1/chat/completions, POST /v1/completions, GET /v1/models,
GET /health, GET /metrics (Prometheus), GET /v1/traces[/{request_id}]
(request span timelines; ``?format=chrome`` exports Perfetto-loadable
trace-event JSON). SSE streaming with client-disconnect propagation into
engine cancellation; a ModelManager maps model name → engines and supports
live add/remove (used by etcd-style discovery later).

Every request opens a root span whose trace id is the request id (echoed
back as the ``x-request-id`` response header); per-stage latencies (TTFT,
inter-token) land in the process StageMetrics and /metrics additionally
merges the stage histograms workers publish to the store.

Reference capability: lib/llm/src/http/service/{service_v2,openai,metrics,
discovery}.rs — axum server, ModelManager, disconnect monitor, Prometheus.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from aiohttp import web

from ..runtime import deadline as dl
from ..runtime.engine import AsyncEngine, Context, EngineError
from ..utils import overload, tracing
from ..utils.prometheus import Registry, render_states, stage_metrics

log = logging.getLogger("dynamo_tpu.http_service")
from .model_card import ModelDeploymentCard
from .protocols.openai import (
    ChatCompletionRequest,
    CompletionRequest,
    ProtocolError,
    SSE_DONE,
    aggregate_chat_chunks,
    aggregate_completion_chunks,
    sse_encode,
)


@dataclass
class ServedModel:
    card: ModelDeploymentCard
    chat_engine: Optional[AsyncEngine] = None
    completion_engine: Optional[AsyncEngine] = None


class ModelManager:
    """Live registry of served models; safe to mutate while serving."""

    def __init__(self):
        self._models: Dict[str, ServedModel] = {}

    def add(self, model: ServedModel) -> None:
        self._models[model.card.name] = model

    def remove(self, name: str) -> None:
        self._models.pop(name, None)

    def get(self, name: str) -> Optional[ServedModel]:
        return self._models.get(name)

    def list(self):
        return list(self._models.values())


class HttpService:
    def __init__(self, manager: Optional[ModelManager] = None,
                 host: str = "0.0.0.0", port: int = 8080, store=None,
                 namespace: Optional[str] = None,
                 router_decisions=None, admission=None, tenants=None):
        self.manager = manager or ModelManager()
        self.host = host
        self.port = port
        # overload control (utils/overload.py): admission gate (DYN_ADMIT_*
        # knobs; inert when none are set) + this process's view of the
        # fleet brownout level (armed against the store by cli/http)
        self.admission = admission if admission is not None \
            else overload.AdmissionController.from_env()
        # per-tenant quotas (x-tenant header): DYN_TENANT_QUOTAS env table,
        # refreshed live from the fleet registry's per-model tenant tables
        # by cli/http. Inert when no tenant has a quota.
        self.tenants = tenants if tenants is not None \
            else overload.TenantAdmission.from_env()
        self.brownout = overload.BrownoutState()
        # fleet plane hooks (cli/http wires both in discovery mode):
        # async () -> {model: status_dict} merging fleet_models/ desired
        # state with the planner's lease-bound fleet_status/ records —
        # GET /v1/models reports per-model state instead of bare names
        self.fleet_status = None
        # () -> set of registry model names: a 404 for a REGISTERED model
        # is labelled with its name (bounded set — the planner's
        # scale-from-zero wake signal); everything else stays "unknown"
        self.known_models = None
        # optional dynstore client: lets /v1/traces fetch spans published by
        # worker processes and /metrics merge their stage histograms —
        # scoped to ``namespace`` when set (a shared store may carry other
        # deployments' dumps, which must not pollute this scrape)
        self.store = store
        self.namespace = namespace
        # optional async callable ``(limit) -> list | None``: fetches the
        # KV router's decision-audit ring (None = router not reachable);
        # unset when the deployment has no router at all
        self.router_decisions = router_decisions
        # set when this frontend also PUBLISHES a stage dump to the store
        # (cli/http discovery mode): /metrics must skip its own published
        # key or the scrape would merge this process's counters twice
        self.stage_worker_id: Optional[int] = None
        # queue-until-boot (DYN_BOOT_WAIT): requests currently parked at
        # ingress waiting for a scaled-to-zero model's replica to boot
        self._boot_parked = 0
        self.stage = stage_metrics()
        self.registry = Registry()
        m = self.registry
        self.m_requests = m.counter(
            "dyn_http_requests_total", "HTTP requests",
            ("model", "endpoint", "status", "tenant"))
        self.m_inflight = m.gauge(
            "dyn_http_inflight_requests", "In-flight requests", ("model",))
        self.m_duration = m.histogram(
            "dyn_http_request_duration_seconds", "Request duration",
            ("model", "endpoint"))
        self.m_tokens = m.counter(
            "dyn_http_output_tokens_total", "Completion tokens produced", ("model",))
        self._runner: Optional[web.AppRunner] = None
        self.app = self._build_app()

    # ------------------------------------------------------------------
    def _build_app(self) -> web.Application:
        app = web.Application()
        app.router.add_post("/v1/chat/completions", self._chat)
        app.router.add_post("/v1/completions", self._completions)
        app.router.add_get("/v1/models", self._models)
        app.router.add_get("/v1/traces", self._list_traces)
        app.router.add_get("/v1/traces/{request_id}", self._get_trace)
        app.router.add_get("/v1/router/decisions", self._router_decisions)
        app.router.add_get("/v1/incidents", self._list_incidents)
        app.router.add_get("/v1/incidents/{incident_id}", self._get_incident)
        app.router.add_get("/v1/flows", self._list_flows)
        app.router.add_get("/health", self._health)
        app.router.add_get("/metrics", self._metrics)
        return app

    async def start(self) -> int:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        # resolve the actual port (port=0 supported for tests)
        for s in site._server.sockets:  # type: ignore[union-attr]
            self.port = s.getsockname()[1]
            break
        return self.port

    async def stop(self) -> None:
        pub = getattr(self, "_stage_pub_task", None)
        if pub is not None:          # discovery-mode stage publish loop
            pub.cancel()
        obs_h = getattr(self, "_obs_handle", None)
        if obs_h is not None:        # discovery-mode flight-recorder plane
            await obs_h.stop()
        if self._runner:
            await self._runner.cleanup()

    async def run_forever(self) -> None:
        await self.start()
        while True:
            await asyncio.sleep(3600)

    # ------------------------------------------------------------------
    async def _health(self, _req: web.Request) -> web.Response:
        return web.json_response(
            {"status": "ok", "models": [m.card.name for m in self.manager.list()]}
        )

    async def _metrics(self, _req: web.Request) -> web.Response:
        text = self.registry.render()
        # per-stage histograms: this process's, plus — in discovery mode —
        # the dumps every worker publishes under metrics_stage/ (component-
        # labelled, merged across replicas)
        states = [("http", self.stage.registry.state_dump())]
        if self.store is not None:
            try:
                from .metrics_aggregator import fetch_stage_states

                states += await fetch_stage_states(
                    self.store, self.namespace,
                    exclude_worker=self.stage_worker_id)
            except Exception:
                log.exception("stage metrics scrape failed")
        text += render_states(states)
        return web.Response(text=text, content_type="text/plain")

    # ------------------------------------------------------------------
    async def _list_traces(self, req: web.Request) -> web.Response:
        try:
            limit = int(req.query.get("limit", "50"))
        except ValueError:
            return _err(400, "limit must be an integer")
        ids = tracing.get_tracer().recent_trace_ids(limit)
        return web.json_response({"traces": ids})

    async def _get_trace(self, req: web.Request) -> web.Response:
        rid = req.match_info["request_id"]
        local = tracing.get_tracer().spans_for(rid)
        remote = []
        if self.store is not None:
            try:
                remote = await tracing.fetch_trace_spans(self.store, rid)
            except Exception:
                log.exception("trace fetch from store failed")
        spans = tracing.merge_spans(local, remote)
        if not spans:
            return _err(404, f"no trace recorded for request {rid!r}")
        if req.query.get("format") == "chrome":
            return web.json_response(tracing.to_chrome_trace(spans))
        return web.json_response(
            {"trace_id": rid, "spans": [s.to_dict() for s in spans]})

    async def _router_decisions(self, req: web.Request) -> web.Response:
        """The KV router's decision audit: per-request score breakdowns
        (overlap/cache_usage/load per candidate, chosen worker, salt) from
        the router's bounded ring. 404 when no router is configured."""
        if self.router_decisions is None:
            return _err(404, "no KV router configured on this frontend")
        try:
            limit = int(req.query.get("limit", "0"))
        except ValueError:
            return _err(400, "limit must be an integer")
        try:
            decisions = await self.router_decisions(limit)
        except Exception as e:  # noqa: BLE001 - surface, don't 500-trace
            log.exception("router decisions fetch failed")
            return _err(502, f"router decisions fetch failed: {e}")
        if decisions is None:
            return _err(404, "router not reachable (no live router "
                             "instance, or none discovered yet)")
        return web.json_response({"decisions": decisions,
                                  "count": len(decisions)})

    async def _list_flows(self, req: web.Request) -> web.Response:
        """The cluster's byte-flow ledger: per-link totals folded from
        every worker's published stage dump (plus this process's own),
        hottest link first — the same matrix ``dyntop`` renders as
        ``links:`` and ``ctl flows`` prints."""
        from ..obs.flows import flows_from_states

        try:
            limit = int(req.query.get("limit", "0"))
        except ValueError:
            return _err(400, "limit must be an integer")
        states = [("http", self.stage.registry.state_dump())]
        if self.store is not None:
            try:
                from .metrics_aggregator import fetch_stage_states

                states += await fetch_stage_states(
                    self.store, self.namespace,
                    exclude_worker=self.stage_worker_id)
            except Exception:
                log.exception("stage dump scrape for /v1/flows failed")
        links = flows_from_states(states)
        if limit > 0:
            links = links[:limit]
        return web.json_response({"links": links, "count": len(links)})

    async def _list_incidents(self, _req: web.Request) -> web.Response:
        """Live incident beacons (flight-recorder capture coordination) —
        the same view ``ctl incident ls`` renders. 404 without a store."""
        if self.store is None:
            return _err(404, "no store configured on this frontend")
        from ..obs import incidents as _incidents

        ns = self.namespace or "dynamo"
        beacons = await _incidents.list_incidents(self.store, ns)
        return web.json_response({"incidents": beacons,
                                  "count": len(beacons)})

    async def _get_incident(self, req: web.Request) -> web.Response:
        """One assembled incident bundle: manifest + per-process ring
        dumps + the trigger's retro-assembled trace."""
        if self.store is None:
            return _err(404, "no store configured on this frontend")
        from ..obs import incidents as _incidents

        iid = req.match_info["incident_id"]
        ns = self.namespace or "dynamo"
        bundle = await _incidents.fetch_bundle(self.store, ns, iid)
        if bundle is None:
            return _err(404, f"no incident {iid!r} (expired or never "
                             f"captured)")
        return web.json_response(bundle)

    async def _models(self, _req: web.Request) -> web.Response:
        now = int(time.time())
        rows = {
            m.card.name: {"id": m.card.name, "object": "model",
                          "created": now, "owned_by": "dynamo_tpu",
                          "context_length": m.card.context_length}
            for m in self.manager.list()
        }
        # fleet view: per-model state (ready/booting/draining/off),
        # replica counts and targets from the registry + the planner's
        # lease-bound status — including registered models with NO live
        # replica (scaled to zero / still booting), which the discovery
        # manager alone cannot see
        if self.fleet_status is not None:
            try:
                for name, st in (await self.fleet_status()).items():
                    row = rows.setdefault(name, {
                        "id": name, "object": "model", "created": now,
                        "owned_by": "dynamo_tpu"})
                    row["state"] = st.get("state", "unknown")
                    # wake_path/wake_seconds: how this model last came
                    # up — "swap" (in-place weight swap, seconds-scale)
                    # or "cold" (full boot) — and what it cost
                    for fld in ("replicas", "target", "component",
                                "chips", "priority", "wake_path",
                                "wake_seconds"):
                        if st.get(fld) is not None:
                            row[fld] = st[fld]
            except Exception:
                log.exception("fleet status fetch failed; serving bare "
                              "model list")
        return web.json_response({
            "object": "list",
            "data": sorted(rows.values(), key=lambda r: r["id"]),
        })

    # ------------------------------------------------------------------
    async def _chat(self, req: web.Request) -> web.StreamResponse:
        return await self._serve(req, "chat")

    async def _completions(self, req: web.Request) -> web.StreamResponse:
        return await self._serve(req, "completions")

    def _count(self, model: str, endpoint: str, status: str,
               tenant: str) -> None:
        """The one request-accounting path: the HTTP counter (tenant
        label bounded to the quota table + 'other') and the per-tenant
        stage counter the fleet-wide tenant burn is computed from."""
        tlabel = self.tenants.label(tenant)
        self.m_requests.inc(model, endpoint, status, tlabel)
        self.stage.tenant_requests.inc(tlabel, status)

    async def _serve(self, req: web.Request, endpoint: str) -> web.StreamResponse:
        started = time.monotonic()
        # ---- overload admission: the cheapest possible shed, decided from
        # headers alone before the body is even read. A rejected request
        # costs microseconds and a 429 + Retry-After — never a queue slot,
        # never a deadline burn. Order: brownout (fleet state), tenant
        # quota (isolation — a hog is shed before it touches the shared
        # caps), then the global admission gate.
        tenant = overload.DEFAULT_TENANT
        try:
            priority = overload.parse_priority(
                req.headers.get(overload.PRIORITY_HEADER))
            tenant = overload.parse_tenant(
                req.headers.get(overload.TENANT_HEADER))
        except ValueError as e:
            self._count("unknown", endpoint, "400", tenant)
            return _err(400, str(e))
        level = self.brownout.level
        tenant_held = False
        shed = overload.brownout_reject(priority, level)
        if shed is None:
            shed = self.tenants.try_admit(tenant, priority)
            tenant_held = shed is None
        if shed is None:
            shed = self.admission.try_admit(priority)
            if shed is not None and tenant_held:
                self.tenants.release(tenant)
                tenant_held = False
        if shed is not None:
            self._count("unknown", endpoint, str(shed.code), tenant)
            return _err_engine(shed)
        try:
            return await self._serve_admitted(req, endpoint, started,
                                              priority, level, tenant)
        finally:
            self.admission.release()
            self.admission.release_kv(req.get("dyn_kv_cost", 0.0))
            self.tenants.release(tenant)

    async def _serve_admitted(self, req: web.Request, endpoint: str,
                              started: float, priority: str, level: int,
                              tenant: str) -> web.StreamResponse:
        model_name = "unknown"
        try:
            body = await req.json()
        # dynalint: ok(swallowed-exception) malformed client JSON: counted
        # through _count (the tenant-labelled request counter) and
        # answered with a 400 — the parse error text is client data
        except Exception:
            self._count(model_name, endpoint, "400", tenant)
            return _err(400, "invalid JSON body")
        if not isinstance(body, dict):
            self._count(model_name, endpoint, "400", tenant)
            return _err(400, "request body must be a JSON object")
        try:
            if endpoint == "chat":
                oai_req = ChatCompletionRequest.from_dict(body)
            else:
                oai_req = CompletionRequest.from_dict(body)
        except ProtocolError as e:
            self._count("unknown", endpoint, "400", tenant)
            return _err(400, str(e))
        except Exception as e:
            # any other parse failure is still the client's malformed input
            self._count("unknown", endpoint, "400", tenant)
            return _err(400, f"malformed request: {e}")
        try:
            timeout = _request_timeout(req)
        except ValueError as e:
            self._count("unknown", endpoint, "400", tenant)
            return _err(400, str(e))
        # brownout degradation (fleet level, store-published): shrink the
        # work an admitted request may cost — cap max_tokens, drop
        # speculative decoding's extra programs
        cap = overload.max_tokens_cap(level)
        if cap is not None:
            oai_req.max_tokens = cap if oai_req.max_tokens is None \
                else min(oai_req.max_tokens, cap)
        if overload.disables_spec(level):
            oai_req.ext["no_spec"] = True
        # byte-honest admission, second gate: with the body read, price
        # the request's KV working set (estimated tokens x per-token
        # bytes) against the in-flight budget — one long-context request
        # consumes its true share of the envelope, not one slot. Released
        # in _serve's finally via the request-scoped cost.
        if self.admission.kv_enabled:
            kv_cost = self.admission.price_kv(
                overload.estimate_request_tokens(oai_req))
            shed = self.admission.try_reserve_kv(kv_cost,
                                                 priority)
            if shed is not None:
                self._count("unknown", endpoint, str(shed.code), tenant)
                return _err_engine(shed)
            req["dyn_kv_cost"] = kv_cost
        model_name = oai_req.model
        engine = self._engine_for(model_name, endpoint)
        if engine is None:
            # label with a constant to keep metric cardinality bounded
            # (model names of 404s are client-controlled) — EXCEPT for
            # fleet-registered models, a bounded set whose 404s are the
            # planner's scale-from-zero wake signal
            known = self.known_models() if self.known_models else ()
            label = model_name if model_name in known else "unknown"
            if label != "unknown":
                # queue-until-boot (DYN_BOOT_WAIT): park the request,
                # bounded and deadline-aware, until the wake signal has
                # booted a replica — scale-from-zero then costs latency
                # instead of a 404 retry storm
                t_park = time.monotonic()
                engine, shed = await self._queue_until_boot(
                    model_name, endpoint, timeout)
                if shed is not None:
                    self._count(label, endpoint, str(shed.code), tenant)
                    return _err_engine(shed)
                if engine is not None and timeout is not None:
                    # the park spent part of the request's end-to-end
                    # budget; the serve gets the remainder, never a
                    # fresh full window
                    timeout = max(timeout - (time.monotonic() - t_park),
                                  0.05)
            if engine is None:
                self._count(label, endpoint, "404", tenant)
                return _err(404, f"model {model_name!r} not found"
                            + (" (registered, no live replica — booting "
                               "or scaled to zero)"
                               if label != "unknown" else ""))

        # end-to-end deadline (x-request-timeout header, DYN_REQUEST_TIMEOUT
        # default): every downstream hop sees it via the context / wire
        # envelope; expiry anywhere surfaces as a 504 naming the stage.
        # The priority class rides the same envelope.
        ctx = Context(deadline=dl.from_timeout(timeout), priority=priority)
        ctx.stamps["received"] = started
        # request-id span: every log line in this async call chain (and in
        # remote workers via the wire context_id) carries ctx.id
        from ..utils.logging_ext import request_id_var
        request_id_var.set(ctx.id)
        # root span: trace id IS the request id; every downstream span —
        # local pipeline stages and remote workers via the wire trace
        # field — stitches under it. GET /v1/traces/{ctx.id} replays it.
        tracer = tracing.get_tracer()
        root = tracer.start_span(f"http:{endpoint}", trace_id=ctx.id,
                                 model=model_name)
        root_token = tracing.current_span_var.set(root.context()) \
            if root is not None else None
        self.m_inflight.inc(model_name)
        status = "200"
        try:
            if oai_req.stream:
                try:
                    resp = await self._stream(req, engine, oai_req, ctx,
                                              model_name, endpoint)
                except (ConnectionResetError, asyncio.CancelledError):
                    status = "499"   # client closed mid-stream
                    raise
                # mid-stream failures can't change the committed 200, but
                # the root span / request counter must reflect them; a
                # pre-commit failure returns a plain 4xx/5xx response
                status = getattr(resp, "_dyn_error_status",
                                 str(resp.status))
                return resp
            chunks = []
            first = True
            try:
                async for ch in dl.guard_stream(
                        engine.generate(oai_req, ctx), ctx.deadline,
                        "http_aggregate", slack=0.5):
                    if "event" in ch:
                        continue  # annotations only meaningful when streaming
                    if "error" in ch:
                        # a pipeline that already yielded chunks reports
                        # failures in-stream; here nothing is committed yet
                        # so it can still be a clean 4xx
                        status = "400"
                        return _err(400, ch["error"]["message"], ctx.id)
                    if first:
                        self._first_chunk(model_name, ctx, time.monotonic())
                        first = False
                    chunks.append(ch)
                    u = ch.get("usage")
                    if u:
                        self.m_tokens.inc(model_name,
                                          amount=u["completion_tokens"])
            except ProtocolError as e:
                status = "400"
                return _err(400, str(e), ctx.id)
            except EngineError as e:
                status = str(e.code)
                return _err_engine(e, ctx.id)
            agg = (aggregate_chat_chunks(chunks) if endpoint == "chat"
                   else aggregate_completion_chunks(chunks))
            return web.json_response(agg,
                                     headers={"x-request-id": ctx.id})
        finally:
            if root_token is not None:
                tracing.current_span_var.reset(root_token)
            tracer.finish(root, status="ok" if status == "200" else "error")
            self.m_inflight.dec(model_name)
            self._count(model_name, endpoint, status, tenant)
            self.m_duration.observe(model_name, endpoint,
                                    value=time.monotonic() - started)

    def _first_chunk(self, model: str, ctx: Context, now: float) -> None:
        """The first chunk is about to be written: time to first token, and
        its last stage, from the first token on the engine's host side to
        here (a remote engine's clock is not ours: no stamp, no stage)."""
        self.stage.ttft.observe(model, value=now - ctx.stamps["received"])
        first_token = ctx.stamps.get("first_token")
        if first_token is not None:
            self.stage.request_stage.observe("post_engine",
                                             value=now - first_token)

    def _engine_for(self, model_name: str,
                    endpoint: str) -> Optional[AsyncEngine]:
        served = self.manager.get(model_name)
        if served is None:
            return None
        return (served.chat_engine if endpoint == "chat"
                else served.completion_engine)

    async def _queue_until_boot(self, model_name: str, endpoint: str,
                                timeout: Optional[float]):
        """Park a request for a fleet-registered model with no live
        replica until one boots: ``(engine, None)`` when a replica
        appeared, ``(None, shed)`` for a typed 503 (park window expired
        while still booting, or the bounded park queue is full), and
        ``(None, None)`` when the feature is off (caller 404s as
        before). Parks are counted per model
        (``dyn_queue_until_boot_total``) and feed the planner's
        unserved-demand wake signal exactly like the 404s they
        replace."""
        from ..utils.knobs import env_float

        wait_s = env_float("DYN_BOOT_WAIT", 0.0, minimum=0.0)
        if wait_s <= 0:
            return None, None
        # deadline-aware: never park past the request's own budget
        # (leave a slice of it for the actual serve)
        if timeout is not None:
            wait_s = min(wait_s, max(timeout * 0.8, 0.0))
        max_parked = int(env_float("DYN_BOOT_WAIT_QUEUE", 64, minimum=0))
        qub = self.stage.queue_until_boot
        if self._boot_parked >= max_parked:
            qub.inc(model_name, "overflow")
            return None, EngineError(
                f"model {model_name!r} is booting and the park queue is "
                f"full ({max_parked} requests already waiting)", 503,
                stage="ingress", reason="boot_queue_full",
                retry_after=2.0)
        qub.inc(model_name, "parked")
        self._boot_parked += 1
        try:
            deadline = time.monotonic() + wait_s
            while True:
                engine = self._engine_for(model_name, endpoint)
                if engine is not None:
                    qub.inc(model_name, "served")
                    return engine, None
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                await asyncio.sleep(min(0.25, remaining))
        finally:
            self._boot_parked -= 1
        qub.inc(model_name, "expired")
        return None, EngineError(
            f"model {model_name!r} has no live replica after waiting "
            f"{wait_s:.1f}s for boot (registered — scale-from-zero in "
            f"progress)", 503, stage="ingress", reason="booting",
            retry_after=2.0)

    async def _stream(self, req: web.Request, engine: AsyncEngine, oai_req,
                      ctx: Context, model: str,
                      endpoint: str) -> web.StreamResponse:
        agen = engine.generate(oai_req, ctx)
        # Pull the first item BEFORE committing the 200/SSE response so that
        # preprocessing failures (context overflow, bad template) still map to
        # a proper 4xx status instead of an error inside a 200 stream — and
        # a pre-first-token deadline expiry to a clean 504.
        try:
            first_item = await dl.wait_for(agen.__anext__(), ctx.deadline,
                                           "http_first_token", slack=0.5)
        except StopAsyncIteration:
            first_item = None
        except ProtocolError as e:
            return _err(400, str(e), ctx.id)
        except EngineError as e:
            return _err_engine(e, ctx.id)
        if isinstance(first_item, dict) and "error" in first_item:
            # a pipeline that reports failures in-stream (tool matcher) may
            # fail before any content chunk; nothing is committed yet so it
            # can still be a proper 4xx
            return _err(400, first_item["error"]["message"], ctx.id)

        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache",
                     "x-request-id": ctx.id},
        )
        await resp.prepare(req)
        first = True
        last_chunk_at: Optional[float] = None
        stage = self.stage
        tracer = tracing.get_tracer()
        sse_span = tracer.start_span("sse.egress", model=model)
        chunks_out = 0

        async def chain():
            if first_item is not None:
                yield first_item
            async for item in agen:
                yield item

        try:
            async for ch in dl.guard_stream(chain(), ctx.deadline,
                                            "http_stream", slack=0.5):
                if "event" in ch:
                    payload = (f"event: {ch['event']}\n"
                               f"data: {json.dumps(ch['data'])}\n\n").encode()
                    await resp.write(payload)
                    continue
                if "error" in ch:
                    # in-band error after chunks were committed: the HTTP
                    # status is already 200, but traces/metrics must not
                    # call this request ok
                    resp._dyn_error_status = "500"
                    await resp.write(sse_encode(json.dumps(ch)))
                    continue
                now = time.monotonic()
                if first:
                    self._first_chunk(model, ctx, now)
                    first = False
                elif last_chunk_at is not None:
                    stage.inter_token.observe(model,
                                              value=now - last_chunk_at)
                last_chunk_at = now
                chunks_out += 1
                u = ch.get("usage")
                if u:
                    self.m_tokens.inc(model, amount=u["completion_tokens"])
                await resp.write(sse_encode(json.dumps(ch)))
            await resp.write(sse_encode(SSE_DONE))
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away: propagate cancellation into the engine.
            # 499 (nginx's client-closed-request): aborted streams are the
            # requests operators trace — they must not read as clean 200s
            resp._dyn_error_status = "499"
            ctx.stop_generating()
            raise
        except ProtocolError as e:
            resp._dyn_error_status = "400"
            await resp.write(sse_encode(json.dumps({"error": {
                "message": str(e), "type": "invalid_request_error"}})))
            await resp.write(sse_encode(SSE_DONE))
        except EngineError as e:
            resp._dyn_error_status = str(e.code)
            await resp.write(sse_encode(json.dumps({"error": {
                "message": str(e), "type": "engine_error", "code": e.code}})))
            await resp.write(sse_encode(SSE_DONE))
        finally:
            if sse_span is not None:
                sse_span.attrs["chunks"] = chunks_out
            tracer.finish(sse_span,
                          status="ok" if getattr(resp, "_dyn_error_status",
                                                 "200") == "200" else "error")
            ctx.stop_generating()
        await resp.write_eof()
        return resp


def _request_timeout(req: web.Request) -> Optional[float]:
    """Per-request deadline budget in seconds: the ``x-request-timeout``
    header when present, else the ``DYN_REQUEST_TIMEOUT`` env default, else
    None (no deadline). A malformed HEADER raises ValueError (the client's
    fault — 400); a malformed env default is the operator's typo and is
    logged and ignored, never inflicted on clients."""
    import os

    raw = req.headers.get("x-request-timeout")
    if raw:
        try:
            t = float(raw)
        except ValueError:
            raise ValueError(f"x-request-timeout: {raw!r} is not a number")
        if not t > 0:
            raise ValueError(f"x-request-timeout must be > 0, got {t}")
        return t
    env = os.environ.get("DYN_REQUEST_TIMEOUT")
    if not env:
        return None
    try:
        t = float(env)
    except ValueError:
        log.warning("ignoring malformed DYN_REQUEST_TIMEOUT=%r", env)
        return None
    return t if t > 0 else None


_ERR_TYPES = {400: "invalid_request_error", 404: "not_found_error",
              429: "overloaded_error", 502: "bad_gateway_error",
              503: "service_unavailable_error", 504: "timeout_error"}

# typed-error fallbacks for EngineErrors raised by layers that predate the
# stage/reason fields (e.g. a bare 503 from the dispatch client): every
# 429/503/504 body names A stage and reason even when the thrower didn't
_FALLBACK_STAGE = {429: "admission", 502: "router", 503: "dispatch"}
_FALLBACK_REASON = {429: "overload", 503: "no_capacity", 504: "deadline"}


def _err(code: int, message: str, request_id: Optional[str] = None, *,
         stage: Optional[str] = None, reason: Optional[str] = None,
         retry_after: Optional[float] = None) -> web.Response:
    """The ONE error-body shape: ``{"error": {message, type, code, stage?,
    reason?, retry_after?}}``. Overload (429) and unavailability (503)
    responses always carry ``Retry-After``; errors for requests that got
    far enough to have an id carry ``x-request-id`` too — failed requests
    are the ones operators trace."""
    import math

    err: Dict[str, Any] = {"message": message,
                           "type": _ERR_TYPES.get(code, "internal_error"),
                           "code": code}
    if stage is not None:
        err["stage"] = stage
    if reason is not None:
        err["reason"] = reason
    headers: Dict[str, str] = {}
    if request_id:
        headers["x-request-id"] = request_id
    if retry_after is None and code in (429, 503):
        retry_after = 1.0
    if retry_after is not None:
        err["retry_after"] = round(float(retry_after), 3)
        headers["Retry-After"] = str(max(1, math.ceil(retry_after)))
    return web.json_response({"error": err}, status=code,
                             headers=headers or None)


def _err_engine(e: Exception,
                request_id: Optional[str] = None) -> web.Response:
    """Typed EngineError -> uniform error response: its stage/reason/
    retry_after (which survive the wire from remote workers) land in the
    body, with per-code fallbacks for untyped throwers."""
    code = getattr(e, "code", 500)
    return _err(code, str(e), request_id,
                stage=getattr(e, "stage", None) or _FALLBACK_STAGE.get(code),
                reason=(getattr(e, "reason", None)
                        or _FALLBACK_REASON.get(code)),
                retry_after=getattr(e, "retry_after", None))
