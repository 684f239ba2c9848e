"""The load generator: a child process that never imports JAX.

    python bench/loadgen.py SPEC.json RESULT.json

Speaks raw HTTP/1.1 to the server's ``POST /v1/completions`` with
``"stream": true`` and reads the chunked server-sent events token by
token, on stdlib ``asyncio``. SPEC.json is ``traffic.generate``'s output
plus ``port`` and ``t0`` (a ``time.monotonic()`` instant; the clock is
shared by every process of the machine).

From ``t0`` the warm-up groups are sent, each group together and the
next once it has finished; then the primed streams (closed loop: each
client's first request). Once every one has its first token (at ``t_p``), the lead-in
runs for ``lead_in_s`` seconds and then the window for ``window_s``.

* open loop: every other request is sent when it is due
  (``t_p + due``), whatever the server does; it is timed from when it
  was due, and how late it was sent is recorded.
* closed loop: each client sends its next request when the previous one
  ends.

No request is sent after the window. Requests due inside it are then
given ``grace_s`` seconds for their first token; every stream is then
cut. Stdout carries one JSON line per event (``window`` as soon as it is
known, ``done`` at the end); RESULT.json holds one record per request
sent: due, sent, HTTP status, each token and its arrival time, and the
server's stream id and the instance it was routed to.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time

now = time.monotonic


def emit(**kv):
    print(json.dumps(kv), flush=True)


class Client:
    def __init__(self, spec: dict):
        self.spec = spec
        self.port = spec["port"]
        self.reqs = {r["id"]: r for r in spec["requests"]}
        self.recs = {}
        self.w0 = self.w1 = None
        self.tasks = []

    async def send(self, r: dict, due: float, client: int = -1):
        rec = {"id": r["id"], "phase": r["phase"], "client": client,
               "due": due, "sent": now(), "status": None, "tokens": [],
               "times": [], "done": False, "error": None,
               "prompt_len": len(r["prompt"]), "max_tokens": r["max_tokens"],
               "temperature": r["temperature"]}
        self.recs[r["id"]] = rec
        body = json.dumps({"prompt": r["prompt"], "max_tokens": r["max_tokens"],
                           "temperature": r["temperature"], "seed": r["seed"],
                           "stream": True}).encode()
        writer = None
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           self.port)
            writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                         b"Content-Type: application/json\r\n"
                         + f"Content-Length: {len(body)}\r\n\r\n".encode()
                         + body)
            await writer.drain()
            status = await reader.readline()
            rec["status"] = int(status.split()[1])
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            if rec["status"] != 200:
                return rec
            while True:
                size = int((await reader.readline()).strip() or b"0", 16)
                if size == 0:
                    break
                payload = await reader.readexactly(size + 2)
                t = now()
                for line in payload.split(b"\n"):
                    if not line.startswith(b"data: "):
                        continue
                    data = line[6:]
                    if data == b"[DONE]":
                        rec["done"] = True
                        continue
                    ev = json.loads(data)
                    if "token" in ev:
                        rec["tokens"].append(ev["token"])
                        rec["times"].append(t)
                    elif "error" in ev:
                        rec["error"] = ev["error"]
                    elif "id" in ev:          # the routing verdict
                        rec["server_id"] = ev["id"]
                        rec["instance"] = ev.get("instance")
        except (OSError, ValueError, asyncio.IncompleteReadError) as e:
            rec["error"] = repr(e)
        finally:
            if writer is not None:
                writer.close()
        return rec

    def _started(self, rid) -> bool:
        """A first token came, or the request ended without one."""
        r = self.recs.get(rid)
        return bool(r and (r["times"] or r["done"] or r["error"]
                           or r["status"] not in (None, 200)))

    async def _until_started(self, ids):
        end = now() + self.spec.get("prime_timeout_s", 900.0)
        while now() < end and not all(self._started(i) for i in ids):
            await asyncio.sleep(0.005)

    def _open_window(self):
        s = self.spec
        self.w0 = now() + s["lead_in_s"]
        self.w1 = self.w0 + s["window_s"]
        emit(event="window", w0=self.w0, w1=self.w1)

    async def open_loop(self):
        s = self.spec
        primes = set(s["primes"])
        self.tasks = [asyncio.create_task(self.send(self.reqs[i], now()))
                      for i in s["primes"]]
        await self._until_started(primes)
        t_p = now()
        self._open_window()

        async def at(r):
            due = t_p + r["due"]
            await asyncio.sleep(max(0.0, due - now()))
            return await self.send(r, due)

        self.tasks += [asyncio.create_task(at(r)) for r in s["requests"]
                       if r["id"] not in primes]
        await asyncio.sleep(max(0.0, self.w1 - now()))

    async def closed_loop(self):
        s = self.spec

        async def client(c: int, ids: list):
            for rid in ids:
                if self.w1 is not None and now() >= self.w1:
                    return
                await self.send(self.reqs[rid], now(), c)

        self.tasks = [asyncio.create_task(client(c, ids))
                      for c, ids in enumerate(s["clients"])]
        await self._until_started(s["primes"])
        self._open_window()
        await asyncio.sleep(max(0.0, self.w1 - now()))

    async def finish(self):
        """Give requests due in the window ``grace_s`` for a first token,
        then cut every stream."""
        end = now() + self.spec["grace_s"]
        while now() < end:
            waiting = [r for r in self.recs.values()
                       if self.w0 <= r["due"] <= self.w1 and not r["times"]
                       and r["status"] in (None, 200) and not r["error"]
                       and not r["done"]]
            if not waiting:
                break
            await asyncio.sleep(0.01)
        for t in self.tasks:
            t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)

    async def warm(self):
        """Each warm-up group together, one group after another; not
        recorded."""
        for i, group in enumerate(self.spec.get("warmup", [])):
            reqs = [dict(r, id=f"warm{i}.{j}", phase="warm")
                    for j, r in enumerate(group)]
            recs = await asyncio.gather(*[self.send(r, now()) for r in reqs])
            for r in recs:
                del self.recs[r["id"]]
                if r["status"] != 200 or r["error"] or not r["done"]:
                    raise RuntimeError(f"warm-up request failed: {r}")

    async def run(self):
        await asyncio.sleep(max(0.0, self.spec["t0"] - now()))
        t = now()
        await self.warm()
        emit(event="warm", groups=len(self.spec.get("warmup", [])),
             seconds=now() - t)
        if self.spec["mode"] == "open":
            await self.open_loop()
        else:
            await self.closed_loop()
        await self.finish()


def main(argv):
    with open(argv[1]) as f:
        spec = json.load(f)
    cl = Client(spec)
    asyncio.run(cl.run())
    with open(argv[2], "w") as f:
        json.dump({"w0": cl.w0, "w1": cl.w1, "t_end": now(),
                   "records": list(cl.recs.values())}, f)
    emit(event="done")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
