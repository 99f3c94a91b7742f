"""Correctness of streamed chunk results against ``reference.py``.

Every push of every client, the warm-up's included, is compared at its
last sample: the embedding and the tenant's logits, and the prediction the
client read.  A seeded sample of pushes (the mix's ``full_every``) is
compared at every sample.  The reference runs the float32 TCN at
"highest" precision over each client's whole stream from its first
sample, on the device after the program's state is freed, in blocks of
``ref_block`` clients; tenant logits are Eq. 6, in float64, over the
embeddings the benchmark enrolled in set-up.

Numbers compared (each with its limit in ``limits/<workload>.json``):

  emb_err    max |served - ref| / max |ref| over the embeddings compared
  tl_err     the same over the tenant logits
  pred_gap   max over pushes of (best ref logit - ref logit of the served
             prediction) / max |ref logit|
  steps_off  pushes whose cumulative step is not the samples pushed
  failed     requests that raised
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.compare import pred_gap, rel_err


def _pushes(run):
    return [[op for op in ops if op.kind == "push" and op.ok]
            for ops in run.ops]


def reference_outputs(run, low=None) -> dict:
    """{id(op): {"emb", "tl", "emb_all"?}} for every push, from the
    reference (at ``low`` operand precision for the control)."""
    mix, src = run.plan.mix, run.source
    prefill = np.asarray(run.bank["prefill"], np.float64)
    protos = {t: (prefill[t].sum(axis=1),
                  np.full(prefill.shape[1], prefill.shape[2], np.float64))
              for t in range(prefill.shape[0])}
    pushes = _pushes(run)
    out = {}
    blk = mix["ref_block"]
    for b0 in range(0, len(pushes), blk):
        rows = pushes[b0:b0 + blk]
        want = []
        for ops in rows:
            pos = []
            for op in ops:
                n = op.result["n"]
                pos += list(range(op.pos, op.pos + n)) if "emb_all" in op.result \
                    else [op.pos + n - 1]
            want.append(pos)
        L = max((max(p) + 1 for p in want if p), default=0)
        if not L:
            continue
        Lp = reference.pad_len(L)
        Pp = reference.pad_len(max(len(p) for p in want), 256)
        x = np.zeros((blk, Lp, 1), np.float32)
        pos = np.zeros((blk, Pp), np.int32)
        for i, ops in enumerate(rows):
            x[i] = src.stream(b0 + i, 0, Lp)
            if want[i]:
                pos[i, :len(want[i])] = want[i]
                pos[i, len(want[i]):] = want[i][-1]
        emb, _ = reference.outputs_at(run.params, run.bn, x, pos, low=low)
        emb = np.asarray(emb, np.float64)
        for i, ops in enumerate(rows):
            at = {p: j for j, p in enumerate(want[i])}
            sums, counts = protos[(b0 + i) % mix["tenants"]]
            for op in ops:
                n = op.result["n"]
                last = emb[i, at[op.pos + n - 1]]
                r = {"emb": last,
                     "tl": reference.proto_logits(last, sums, counts, low)}
                if "emb_all" in op.result:
                    r["emb_all"] = emb[i, [at[p] for p in
                                           range(op.pos, op.pos + n)]]
                out[id(op)] = r
    return out


def fabricate(run, low) -> None:
    """Put the reference at ``low`` precision in the program's place: the
    control, which the limits must fail."""
    ref = reference_outputs(run, low)
    for ops in _pushes(run):
        for op in ops:
            r = ref[id(op)]
            op.result.update(emb=r["emb"], tl=r["tl"],
                             pred=int(np.argmax(r["tl"])),
                             step=op.pos + op.result["n"])
            if "emb_all" in r:
                op.result["emb_all"] = r["emb_all"]


def numbers(run, ref) -> dict:
    pushes = [op for ops in _pushes(run) for op in ops]
    emb = [(op.result["emb"], ref[id(op)]["emb"]) for op in pushes]
    emb += [(op.result["emb_all"], ref[id(op)]["emb_all"])
            for op in pushes if "emb_all" in op.result]
    tl = [(op.result["tl"], ref[id(op)]["tl"]) for op in pushes]
    gap = pred_gap([op.result["pred"] for op in pushes], [w for _, w in tl])
    return {"emb_err": rel_err(emb), "tl_err": rel_err(tl), "pred_gap": gap,
            "steps_off": float(sum(op.result["step"] != op.pos + op.result["n"]
                                   for op in pushes)),
            "failed": float(sum(not op.ok for ops in run.ops for op in ops))}


def compare(run) -> dict:
    return numbers(run, reference_outputs(run))
