"""Correctness of continual learning served live, against ``reference.py``.

Each client replays, in order, what its tenant's bank saw: the set-up
prefill (the embedded shots the benchmark enrolled, summed in float64),
then every enrollment of raw shots, whose embeddings the reference
computes, folded into the class's running mean (Eq. 6).  Every query is
compared at its last sample against the reference embedding of its glyph
and the tenant's prototype logits at that moment; a seeded sample of
queries (the mix's ``full_every``) is compared at every sample.  The
reference runs the float32 TCN at "highest" precision on the device after
the program's state is freed, in blocks of ``ref_block`` rows.

Numbers compared (each with its limit in ``limits/<workload>.json``):

  emb_err    max |served - ref| / max |ref| over the query embeddings
  tl_err     the same over the tenants' logits
  pred_gap   max over queries of (best ref logit - ref logit of the served
             prediction) / max |ref logit|
  way_off    enrollments acknowledged with another way than their label's
  steps_off  queries whose step is not the glyph's length
  failed     requests that raised
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.compare import pred_gap, rel_err


def _embed(run, xs, low, *, all_positions=False):
    """Reference embeddings of sequences xs (N, T, 1): the last sample's
    (N, V), or every sample's (N, T, V)."""
    blk = run.plan.mix["ref_block"]
    out = []
    T = xs.shape[1] if len(xs) else 0
    pos = np.arange(T) if all_positions else np.array([T - 1])
    for i in range(0, len(xs), blk):
        x = xs[i:i + blk]
        n = len(x)
        x = np.pad(x, ((0, blk - n), (0, 0), (0, 0)))
        p = np.broadcast_to(pos, (blk, len(pos))).astype(np.int32)
        emb, _ = reference.outputs_at(run.params, run.bn, x, p, low=low)
        out.append(np.asarray(emb, np.float64)[:n])
    if not out:
        return np.zeros((0, len(pos), 0))
    e = np.concatenate(out)
    return e if all_positions else e[:, 0]


def reference_outputs(run, low=None) -> dict:
    mix, src = run.plan.mix, run.source
    ops = [op for c in run.ops for op in c if op.ok]
    enrolls = [op for op in ops if op.kind == "enroll"]
    queries = [op for op in ops if op.kind == "push"]
    shots = np.concatenate([src.enroll_input(op)[1] for op in enrolls]) \
        if enrolls else np.zeros((0, mix["chunk"], 1), np.float32)
    e_emb = _embed(run, shots, low).reshape(len(enrolls), mix["shots"], -1) \
        if enrolls else None
    q_x = np.stack([src.push_input(op) for op in queries])
    q_emb = _embed(run, q_x, low)
    full = [i for i, op in enumerate(queries) if "emb_all" in op.result]
    q_all = _embed(run, q_x[full], low, all_positions=True) if full else []
    e_of = {id(op): e_emb[i] for i, op in enumerate(enrolls)}
    q_of = {id(op): i for i, op in enumerate(queries)}
    all_of = {full_i: j for j, full_i in enumerate(full)}
    prefill = np.asarray(run.bank["prefill"], np.float64)
    out = {}
    for client_ops in run.ops:
        if not client_ops:
            continue
        t = client_ops[0].client % mix["tenants"]
        sums = prefill[t].sum(axis=1)
        counts = np.full(mix["ways"], prefill.shape[2], np.float64)
        for op in client_ops:
            if not op.ok:
                continue
            if op.kind == "enroll":
                label = op.result["label"]
                sums[label] += e_of[id(op)].sum(0)
                counts[label] += mix["shots"]
            elif op.kind == "push":
                i = q_of[id(op)]
                r = {"emb": q_emb[i], "tl": reference.proto_logits(
                    q_emb[i], sums, counts, low)}
                if i in all_of:
                    r["emb_all"] = q_all[all_of[i]]
                out[id(op)] = r
    return out


def fabricate(run, low) -> None:
    """Put the reference at ``low`` precision in the program's place: the
    control, which the limits must fail."""
    ref = reference_outputs(run, low)
    for c in run.ops:
        for op in c:
            if op.kind == "push" and op.ok:
                r = ref[id(op)]
                op.result.update(emb=r["emb"], tl=r["tl"],
                                 pred=int(np.argmax(r["tl"])),
                                 step=op.result["n"])
                if "emb_all" in r:
                    op.result["emb_all"] = r["emb_all"]
            elif op.kind == "enroll" and op.ok:
                op.result["way"] = op.result["label"]


def numbers(run, ref) -> dict:
    ops = [op for c in run.ops for op in c]
    queries = [op for op in ops if op.kind == "push" and op.ok]
    emb = [(op.result["emb"], ref[id(op)]["emb"]) for op in queries]
    emb += [(op.result["emb_all"], ref[id(op)]["emb_all"])
            for op in queries if "emb_all" in op.result]
    tl = [(op.result["tl"], ref[id(op)]["tl"]) for op in queries]
    gap = pred_gap([op.result["pred"] for op in queries], [w for _, w in tl])
    return {"emb_err": rel_err(emb), "tl_err": rel_err(tl), "pred_gap": gap,
            "way_off": float(sum(op.result["way"] != op.result["label"]
                                 for op in ops
                                 if op.kind == "enroll" and op.ok)),
            "steps_off": float(sum(op.result["step"] != op.result["n"]
                                   for op in queries)),
            "failed": float(sum(not op.ok for op in ops))}


def compare(run) -> dict:
    return numbers(run, reference_outputs(run))
