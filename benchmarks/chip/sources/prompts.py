"""Chat requests: a system prefix per tenant, a user turn, an answer.

Every request of a run comes from the seed.  Request ``k`` (0, 1, ...)
belongs to tenant ``k % tenants``: its prompt is the tenant's system
prefix of ``prefix_tokens`` ids and a user turn, and it is answered
greedily, one push of a budget of 1 token at a time.  Turns and answers
are lognormal, median ``<kind>_median`` and shape ``<kind>_sigma``,
rounded and clipped to ``<kind>_min``..``<kind>_max`` (``kind``: ``turn``,
``answer``).  The lengths are the ``length_pool`` quantiles of that
lognormal, dealt out in cycles of ``length_pool`` requests, each cycle in
an order the seed draws: every seed sends the same set of lengths, in
another order, so the seed changes the order and the ids, not the amount
of work.  Ids are uniform in ``[0, vocab_size)`` of the configuration's
``model``.  The same seed gives the same requests, however far a run gets.

A chat mix takes its medians from a public trace of served conversations:
the Azure LLM inference trace of 2023 (github.com/Azure/AzurePublicDataset),
whose median prompt is 1020 tokens and median answer 129 (Splitwise,
arXiv:2311.18677, section III).  The trace fits no shape and holds no
prefixes, so a mix's ``*_sigma`` and ``prefix_tokens`` are assumed
(PERF.md, section 2).

Arrivals.  With ``rate`` (requests a second) the loop is open: request
``k`` is due at the window's start plus the first ``k`` gaps, the gaps
being the ``length_pool`` quantiles of an exponential of mean
``1 / rate``, dealt in cycles like the lengths (so each cycle of requests
spans the same time).  The ``clients`` take the due requests in order,
each serving one request at a time, as a server admits requests to its
slots; a request's time to its first token runs from when it was due, so
it holds the wait for a free client.  Requests not yet taken when the
window closes are not sent.  Without ``rate`` the loop is closed: a
client takes the next request once its answer is complete.

Set-up opens one session a client (the first session of a tenant fills
the prefix cache with the tenant's prefix), then opens and closes two
prefix-less sessions whose turns make every prefill chunk length the
window can use, so that none compiles inside it, and takes each session's
first token.  A closed loop's set-up sessions are requests ``0`` to
``clients - 1``, answered in the window; an open loop's carry prompts of
their own and are closed before it, so its window starts with no session
open.  A client's loop is ``["chat"]``: each op is one step of a request,
so the window closes one step after its deadline, not one answer:

  chat   the client's next step, and the op takes its kind:
         ``open_prompt`` opens the client's next request and takes its
         first token (the op holds it, ``tokens``, ``request`` and
         ``ttft_s``); ``push`` pushes a budget of 1 token and holds the
         token it returned; ``close`` closes a session whose answer is
         complete; ``wait`` (an open loop) waits for the window, or for a
         request due after it, and sends nothing

Mix keys read here: ``clients``, ``tenants``, ``prefix_tokens``,
``turn_*``, ``answer_*``, ``length_pool``, ``seq_cap``, ``rate``.
"""

from __future__ import annotations

import asyncio
import statistics
import time

import numpy as np

from benchmarks.chip.generator import Op, close_query


def quantiles(n: int, inv_cdf) -> np.ndarray:
    """The ``n`` quantiles of a distribution, at the middles of ``n``
    equal shares."""
    return np.array([inv_cdf((k + 0.5) / n) for k in range(n)])


def lengths(mix: dict, kind: str) -> np.ndarray:
    """The ``length_pool`` lognormal quantiles of ``kind``, rounded and
    clipped."""
    z = quantiles(mix["length_pool"], statistics.NormalDist().inv_cdf)
    x = np.rint(mix[f"{kind}_median"] * np.exp(mix[f"{kind}_sigma"] * z))
    return np.clip(x, mix[f"{kind}_min"], mix[f"{kind}_max"]).astype(np.int64)


def _tokens(out) -> list[int]:
    if len(out) != 1:
        raise RuntimeError(f"a push of 1 token returned {len(out)}")
    return [int(out[0])]


async def _push(clients, op: Op, sid: int) -> None:
    op.kind = "push"
    op.t0 = time.perf_counter()
    out = await clients.plane.push(sid, 1)
    op.t1 = time.perf_counter()
    op.result = {"tokens": _tokens(out)}


async def _wait(op: Op, until: float) -> None:
    op.kind = "wait"
    op.in_window = False        # it sends nothing: no request of the window
    op.t0 = time.perf_counter()
    await asyncio.sleep(max(0.0, until - op.t0))
    op.t1 = time.perf_counter()


async def _open(clients, op: Op) -> None:
    c, src = op.client, clients.source
    due = None
    if src.rate:
        if not op.in_window:        # an open loop sends nothing before it
            return await _wait(op, 0.0)
        now = time.perf_counter()
        if src.t0 is None:
            src.t0 = now
        due = src.t0 + src.arrival(src.next)
        if due >= clients.deadline:
            return await _wait(op, clients.deadline)
    k = src.next
    src.next += 1
    if due is not None:
        await asyncio.sleep(max(0.0, due - time.perf_counter()))
    op.kind = "open_prompt"
    prompt = src.prompt(k)
    op.t0 = time.perf_counter()
    sid = clients.query[c] = await clients.plane.open_session(
        prompt, tenant=src.tenant(k))
    first = _tokens(await clients.plane.push(sid, 1))
    op.t1 = time.perf_counter()
    src.request[c], src.made[c] = k, 1
    op.result = {"request": k, "prompt_len": int(prompt.size),
                 "tokens": first,
                 "ttft_s": op.t1 - (op.t0 if due is None else due)}


async def chat(clients, op: Op) -> None:
    c, src = op.client, clients.source
    sid = clients.query[c]
    if sid is None:
        await _open(clients, op)
    elif src.made[c] >= src.answer_len(src.request[c]):
        op.kind = "close"
        await close_query(clients, op)
    else:
        await _push(clients, op, sid)
        src.made[c] += 1


class Source:
    """The requests of one run, all from the seed."""

    VERBS = {"chat": chat}

    def __init__(self, mix: dict, model: dict, seed: int):
        self.mix, self.seed = mix, seed
        self.vocab = model["vocab_size"]
        longest = mix["prefix_tokens"] + mix["turn_max"] + mix["answer_max"]
        if longest > mix["seq_cap"]:
            raise ValueError(f"prefix, longest turn and longest answer take "
                             f"{longest} positions, over seq_cap "
                             f"{mix['seq_cap']}")
        self.prefix = self._ids((mix["tenants"], mix["prefix_tokens"]), 3)
        self.pool = {"turn": lengths(mix, "turn"),
                     "answer": lengths(mix, "answer")}
        self.rate = mix.get("rate")
        if self.rate:
            self.pool["gap"] = quantiles(
                mix["length_pool"],
                lambda u: -np.log1p(-u) / self.rate)
        self._orders: dict[tuple[str, int], np.ndarray] = {}
        self._arrivals: dict[int, np.ndarray] = {}
        self.t0: float | None = None   # an open loop's window start
        self.next = 0                  # the next request to take
        n = mix["clients"]
        self.request = [0] * n         # each client's request, and the
        self.made = [0] * n            # tokens it has returned

    def _ids(self, shape, *salt: int) -> np.ndarray:
        return np.random.default_rng([int(self.seed), *salt]).integers(
            0, self.vocab, shape, dtype=np.int32)

    def _deal(self, kind: str, k: int):
        """Request ``k``'s value of ``kind``: cycle ``k // n`` deals the
        pool in an order of its own, drawn from the seed."""
        pool = self.pool[kind]
        cycle, i = divmod(k, len(pool))
        if (kind, cycle) not in self._orders:
            salt = ("turn", "answer", "gap").index(kind)
            self._orders[kind, cycle] = np.random.default_rng(
                [int(self.seed), 4, salt, cycle]).permutation(len(pool))
        return pool[self._orders[kind, cycle][i]]

    def turn_len(self, k: int) -> int:
        return int(self._deal("turn", k))

    def answer_len(self, k: int) -> int:
        return int(self._deal("answer", k))

    def arrival(self, k: int) -> float:
        """Seconds from an open loop's window start to request ``k``'s."""
        n = len(self.pool["gap"])
        cycle, i = divmod(k, n)
        if cycle not in self._arrivals:
            gaps = [self._deal("gap", cycle * n + j) for j in range(n)]
            self._arrivals[cycle] = cycle * self.pool["gap"].sum() \
                + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return float(self._arrivals[cycle][i])

    def tenant(self, k: int) -> int:
        return k % self.mix["tenants"]

    def prompt(self, k: int) -> np.ndarray:
        """Request ``k``'s prompt: its tenant's prefix, then the user
        turn."""
        turn = self._ids(self.turn_len(k), 5, k)
        return np.concatenate([self.prefix[self.tenant(k)], turn])

    def open_kwargs(self, clients, c: int) -> dict:
        """Client ``c``'s set-up session: request ``c`` in a closed loop,
        a prompt of its own (the shortest turn) in an open one."""
        if not self.rate:
            return {"prompt": self.prompt(c), "tenant": self.tenant(c)}
        t = self.tenant(c)
        turn = self._ids(self.mix["turn_min"], 7, c)
        return {"prompt": np.concatenate([self.prefix[t], turn]), "tenant": t}

    async def setup(self, clients, params, bn) -> dict:
        plane, mix = clients.plane, self.mix
        # bodies of p and p - 1 ids use every power-of-two prefill chunk up
        # to p, and no body of the window is 2p long (on a full grid, each
        # parks an idle set-up session until the warm-up resumes it)
        p = 1 << ((mix["prefix_tokens"] + mix["turn_max"] - 1).bit_length()
                  - 1)
        for i, n in enumerate((p + 1, p)):
            sid = await plane.open_session(self._ids(n, 6, i), tenant=0)
            await plane.close(sid)
        for out in await asyncio.gather(*(plane.push(sid, 1)
                                          for sid in clients.sessions)):
            _tokens(out)
        if self.rate:
            for sid in clients.sessions:
                await plane.close(sid)
        else:
            clients.query = list(clients.sessions)
            self.request = list(range(mix["clients"]))
            self.made = [1] * mix["clients"]
            self.next = mix["clients"]
        return {}
