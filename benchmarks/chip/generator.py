"""The one traffic generator: closed-loop clients driven by a mix's data.

A traffic mix (``traffic/<name>.json``) names its inputs, the deployment
it needs (slots, tenants, ways, shots) and the ``loop`` each client
repeats until the window closes.  ``"inputs": "<kind>"`` is the module
``sources/<kind>.py``, found by name: its ``Source(mix, model, seed)``
makes every input of the run from the seed, and its ``setup`` coroutine
fills the tenants' banks.  The verbs of a loop:

  push    push the client's next ``chunk`` samples and wait for the result
          (to its query session if one is open, else to its own session)
  enroll  enroll the client's next ``shots`` shots of a class, label-keyed
  open    open a query session for the client's tenant
  close   close the query session

A source adds verbs of its own, or replaces these, through a ``VERBS``
mapping of names to ``async def verb(clients, op)``.

Client ``c`` holds one long-lived session of tenant ``c % tenants``, opened
in set-up with ``tenant=`` alone, or with the keyword arguments of
``plane.open_session`` that the source's ``open_kwargs(clients, c)``
gives where it has one (an LM session's prompt).  Everything a client
sends comes from the seed: the same seed gives the same sequence of
requests, however far a run gets through it.
Every op is logged with its inputs' indices, its timing and what it
returned, for the correctness check and the metrics.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Op:
    kind: str
    client: int
    loop: int
    pos: int = 0                 # push: first sample of the chunk
    t0: float = 0.0
    t1: float = 0.0
    in_window: bool = False
    ok: bool = True
    result: dict = field(default_factory=dict)


async def push(clients, op: Op) -> None:
    c = op.client
    sid = clients.query[c] if clients.query[c] is not None \
        else clients.sessions[c]
    x = clients.source.push_input(op)
    op.t0 = time.perf_counter()
    res = await clients.plane.push(sid, x)
    op.t1 = time.perf_counter()
    clients.pos[c] += len(x)
    # copies of the rows the check reads: a view would keep the whole
    # result alive, and the log would grow by every result of the window
    last = lambda a: None if a is None else np.array(a[-1])
    op.result = {
        "n": len(x), "step": res["step"], "pred": res["pred"],
        "emb": last(res["emb"]), "logits": last(res["logits"]),
        "tl": last(res["tenant_logits"])}
    if clients.keep_full(op):
        op.result["emb_all"] = res["emb"]


async def enroll(clients, op: Op) -> None:
    label, shots = clients.source.enroll_input(op)
    op.t0 = time.perf_counter()
    way = await clients.plane.enroll(clients.sessions[op.client], shots,
                                     label=label)
    op.t1 = time.perf_counter()
    op.result = {"label": label, "way": way}


async def open_query(clients, op: Op) -> None:
    op.t0 = time.perf_counter()
    clients.query[op.client] = await clients.plane.open_session(
        tenant=clients.tenant[op.client])
    op.t1 = time.perf_counter()


async def close_query(clients, op: Op) -> None:
    op.t0 = time.perf_counter()
    await clients.plane.close(clients.query[op.client])
    clients.query[op.client] = None
    op.t1 = time.perf_counter()


VERBS = {"push": push, "enroll": enroll, "open": open_query,
         "close": close_query}


class Clients:
    """The closed-loop clients of one run over a ``ServingPlane``."""

    def __init__(self, plane, source, mix: dict):
        self.plane, self.source, self.mix = plane, source, mix
        self.n = mix["clients"]
        self.tenant = [c % mix["tenants"] for c in range(self.n)]
        self.sessions: list[int] = []
        self.query: list[int | None] = [None] * self.n
        self.loops = [0] * self.n
        self.pos = [0] * self.n
        self.log: list[list[Op]] = [[] for _ in range(self.n)]
        self.keep_every = int(mix.get("full_every", 0))
        self.verbs = {**VERBS, **getattr(source, "VERBS", {})}
        self.deadline: float | None = None    # the window's, once it runs

    async def open_all(self):
        kwargs = getattr(self.source, "open_kwargs",
                         lambda clients, c: {"tenant": clients.tenant[c]})
        self.sessions = [await self.plane.open_session(**kwargs(self, c))
                         for c in range(self.n)]

    def keep_full(self, op: Op) -> bool:
        """Whether to keep a push's whole result: a seeded 1-in-
        ``full_every`` sample of pushes (0: none)."""
        if not self.keep_every:
            return False
        h = (op.client * 1_000_003 + op.loop * 7_919 + self.source.seed) % 2**31
        return h % self.keep_every == 0

    async def _op(self, kind: str, c: int, window: bool) -> Op:
        op = Op(kind, c, self.loops[c], pos=self.pos[c], in_window=window)
        try:
            if kind not in self.verbs:
                raise ValueError(f"unknown op {kind!r} in the mix's loop")
            await self.verbs[kind](self, op)
        except Exception as e:  # a failed request: counted, never retried
            op.t0 = op.t0 or time.perf_counter()
            op.t1 = time.perf_counter()
            op.ok = False
            op.result = {"error": f"{type(e).__name__}: {e}"}
        self.log[c].append(op)
        return op

    async def one_loop(self, c: int, window: bool = False) -> None:
        for kind in self.mix["loop"]:
            await self._op(kind, c, window)
        self.loops[c] += 1

    async def warm_up(self) -> None:
        """Each client's first loop, all at once: every program the
        window uses is compiled here."""
        await asyncio.gather(*(self.one_loop(c) for c in range(self.n)))

    async def run(self, seconds: float) -> tuple[float, float]:
        """Loop every client until ``seconds`` have passed, then let the
        requests in flight finish.  Returns (start, end of the last op)."""
        start = time.perf_counter()
        deadline = self.deadline = start + seconds

        async def loop(c):
            while time.perf_counter() < deadline:
                await self.one_loop(c, window=True)

        await asyncio.gather(*(loop(c) for c in range(self.n)))
        return start, time.perf_counter()

    def window_ops(self, kind: str | None = None) -> list[Op]:
        return [op for ops in self.log for op in ops
                if op.in_window and (kind is None or op.kind == kind)]


async def enroll_embedded(clients: Clients, emb) -> dict:
    """Enroll (tenants, ways, shots, V) embeddings into every tenant's
    bank through the plane, way ``j`` under label ``j``, the tenants of
    one way at once.  Returns what the checks rebuild the bank from."""
    plane, mix = clients.plane, clients.mix
    first = {}
    for c, t in enumerate(clients.tenant):
        first.setdefault(t, clients.sessions[c])
    for way in range(mix["ways"]):
        await asyncio.gather(*(
            plane.enroll(first[t], emb[t, way], embedded=True, label=way)
            for t in range(mix["tenants"])))
    return {"prefill": emb}
