"""The client: what a user of the server sends, on real sockets.

One thread, one asyncio loop, one aiohttp session. Requests are sent open
loop: each at the time the schedule says it is due, whether or not earlier
ones have been answered, and every latency is taken from the DUE time, so a
stall is charged to the requests it delays. How late each request left is
recorded (``sent - due``): a starved generator must not read as a fast
server.

From the program the client takes only what a user or an operator can see:
response bodies, the ``x-tpusc-trace`` response header (the request's own
span subtree, sent back when the request carries a ``traceparent``), the
Prometheus text, ``/monitoring/engine`` and ``/monitoring/tenants``.
"""

from __future__ import annotations

import asyncio
import base64
import hashlib
import json
import secrets
import time
import zlib
from typing import Any

import aiohttp

TRACE_HEADER = "x-tpusc-trace"


def decode_span(header: str | None) -> dict | None:
    """The span subtree in an ``x-tpusc-trace`` header (zlib + urlsafe
    base64 JSON), or None."""
    if not header:
        return None
    try:
        return json.loads(zlib.decompress(base64.urlsafe_b64decode(header)))
    except (ValueError, zlib.error):
        return None


def find_spans(span: dict | None, name: str) -> list[dict]:
    """Every span called ``name`` in the subtree, depth first."""
    if not span:
        return []
    hits = [span] if span.get("name") == name else []
    for child in span.get("children", ()):
        hits += find_spans(child, name)
    return hits


def parse_prometheus(text: str) -> dict[str, float]:
    """Prometheus text -> {'name{labels}': value}."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            try:
                out[key] = float(val)
            except ValueError:
                pass
    return out


def metric_sum(metrics: dict[str, float], name: str, *labels: str) -> float:
    """Sum of the samples of ``name`` whose label set holds every ``labels``
    fragment (``'tier="host"'``). The name must end at ``{`` or the key's
    end, so ``x_total`` never sums ``x_total_peak``."""
    return sum(v for k, v in metrics.items()
               if (k == name or k.startswith(name + "{"))
               and all(lab in k for lab in labels))


def _traceparent() -> str:
    return f"00-{secrets.token_hex(16)}-{secrets.token_hex(8)}-01"


class Client:
    """Async requests against one node's REST port. All times are
    ``time.monotonic()`` seconds."""

    def __init__(self, rest_port: int, timeout_s: float) -> None:
        self.base = f"http://127.0.0.1:{rest_port}"
        self.timeout_s = timeout_s
        self.session: aiohttp.ClientSession | None = None

    async def __aenter__(self) -> "Client":
        self.session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=self.timeout_s),
            connector=aiohttp.TCPConnector(limit=0))
        return self

    async def __aexit__(self, *exc) -> None:
        await self.session.close()

    async def wait_ready(self, deadline_s: float = 180.0) -> None:
        end = time.monotonic() + deadline_s
        while time.monotonic() < end:
            try:
                async with self.session.get(self.base + "/healthz") as r:
                    if r.status == 200:
                        return
            except aiohttp.ClientError:
                pass
            await asyncio.sleep(0.1)
        raise RuntimeError("the server never answered /healthz")

    async def get_json(self, path: str) -> Any:
        async with self.session.get(self.base + path) as r:
            r.raise_for_status()
            return await r.json()

    async def prometheus(self) -> dict[str, float]:
        async with self.session.get(
                self.base + "/monitoring/prometheus/metrics") as r:
            r.raise_for_status()
            return parse_prometheus(await r.text())

    async def observe(self) -> dict[str, Any]:
        """What an operator can read, in one snapshot."""
        return {
            "t": time.monotonic(), "t_wall": time.time(),
            "prom": await self.prometheus(),
            "tenants": (await self.get_json("/monitoring/tenants"))["tenants"],
        }

    async def engine_steps(self) -> list[dict]:
        snap = await self.get_json("/monitoring/engine?reset=0&n=2048")
        return [s for m in snap["models"].values() for s in m["steps"]]

    async def generate(self, tenant: str, prompt, max_new: int,
                       due: float | None = None, index: int = -1,
                       rec: dict | None = None) -> dict:
        """Greedy ``:generate?stream=true`` -> the request's record: the
        arrival time of every ``{"token"}`` frame, the done frame's row."""
        if rec is None:
            rec = new_record("generate", tenant, index, due, len(prompt), max_new)
        body = {"input_ids": [list(prompt)], "max_new_tokens": int(max_new)}
        try:
            async with self.session.post(
                f"{self.base}/v1/models/{tenant}/versions/1:generate?stream=true",
                json=body, headers={"traceparent": _traceparent()},
            ) as resp:
                rec["status"] = resp.status
                rec["span"] = decode_span(resp.headers.get(TRACE_HEADER))
                if resp.status != 200:
                    rec["error"] = (await resp.text())[:300]
                    return rec
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        continue
                    now = time.monotonic()
                    frame = json.loads(raw[5:])
                    if "token" in frame:
                        rec["token_t"].append(now)
                        rec["tokens"].append(int(frame["token"]))
                    elif frame.get("done"):
                        rec["done_row"] = frame["tokens"][0]
                        rec["end"] = now
                    elif "error" in frame:
                        rec["error"] = str(frame)[:300]
            rec["ok"] = (rec["error"] is None
                         and rec["done_row"] == rec["tokens"]
                         and len(rec["tokens"]) == max_new)
            if not rec["ok"] and rec["error"] is None:
                rec["error"] = (f"{len(rec['tokens'])} token frames, asked "
                                f"{max_new}, done frame {rec['done_row'] is not None}")
        except asyncio.CancelledError:
            rec["cancelled"] = True
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        return rec

    async def predict(self, tenant: str, prompt, due: float | None = None,
                      index: int = -1, keep_body: bool = False,
                      rec: dict | None = None) -> dict:
        """REST ``:predict`` of one sequence -> the request's record; the
        body is kept as a digest (and whole, on request)."""
        if rec is None:
            rec = new_record("predict", tenant, index, due, len(prompt), 0)
        body = {"inputs": {"input_ids": [list(prompt)]}}
        try:
            async with self.session.post(
                f"{self.base}/v1/models/{tenant}/versions/1:predict",
                json=body, headers={"traceparent": _traceparent()},
            ) as resp:
                raw = await resp.read()
                rec["end"] = time.monotonic()
                rec["status"] = resp.status
                rec["span"] = decode_span(resp.headers.get(TRACE_HEADER))
                if resp.status != 200:
                    rec["error"] = raw[:300].decode(errors="replace")
                    return rec
                rec["digest"] = hashlib.blake2b(raw, digest_size=16).hexdigest()
                if keep_body:
                    rec["body"] = raw
                rec["ok"] = True
        except asyncio.CancelledError:
            rec["cancelled"] = True
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        return rec


def new_record(verb: str, tenant: str, index: int, due: float | None,
            prompt_len: int, max_new: int) -> dict:
    now = time.monotonic()
    return {"verb": verb, "tenant": tenant, "index": index,
            "due": now if due is None else due, "sent": now, "end": None,
            "prompt_len": prompt_len, "max_new": max_new, "status": None,
            "ok": False, "error": None, "cancelled": False, "span": None,
            "token_t": [], "tokens": [], "done_row": None, "digest": None}


async def replay(client: Client, schedule, tenant_names: list[str],
                 t0: float, seconds: float, drain_s: float) -> list[dict]:
    """Send ``schedule`` open loop from ``t0`` for ``seconds``; then wait up
    to ``drain_s`` for the answers still on their way. What is unanswered even
    then is cancelled, keeps what it had received and counts as FAILED (the
    run is then not ``correct``): size ``drain_s`` for the cell's longest
    request, so that only a stream the program has stalled meets it.
    -> records, in order."""
    records: list[dict | None] = [None] * len(schedule)
    tasks: list[asyncio.Task] = []

    async def one(req) -> None:
        due = t0 + req.at_s
        tenant = tenant_names[req.tenant]
        verb = "generate" if req.max_new > 0 else "predict"
        # the record exists from the moment of sending, so that a request
        # cancelled at the end still shows what it had received
        rec = records[req.index] = new_record(
            verb, tenant, req.index, due, len(req.prompt), req.max_new)
        if verb == "generate":
            await client.generate(tenant, req.prompt, req.max_new, rec=rec)
        else:
            await client.predict(tenant, req.prompt, rec=rec)

    for req in schedule:
        delay = t0 + req.at_s - time.monotonic()
        if req.at_s >= seconds:
            break
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(one(req)))
    rest = t0 + seconds - time.monotonic()
    if rest > 0:
        await asyncio.sleep(rest)
    if tasks:
        _done, pending = await asyncio.wait(tasks, timeout=max(drain_s, 0.001))
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return [r for r in records if r is not None]
