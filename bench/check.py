"""The comparison that decides ``correct``.

Two layers are compared, on what the timed window itself served:

* the engine's bookkeeping, for every request of every agent due inside the
  window, exactly: every such agent completed, every request streamed
  exactly its decode budget, every token lies in the vocabulary, the first
  token its prefill sampled was seen, and the engine counted the tokens
  that were streamed;
* the model step, on a sample of the finished requests drawn from the seed,
  the longest among them: the plain float32 reference of the
  configuration's family (``reference/<family>.py``) runs
  once over each prompt followed by its served tokens, and the number
  compared is the widest gap by which a served token's logit lies below
  the reference's best at that position.  The served tokens are greedy, so
  a served model that computes what the reference computes shows gaps
  of rounding size only.

The control (``model_step(..., control=True)``, read by ``control.py``
only) is the same reference computed in float8, put in the program's place:
at each position of the same sequences, the gap under the float32
reference of the token the float8 pass puts first.
"""

from __future__ import annotations

import numpy as np


def bookkeeping(run) -> dict:
    """Counts of faults (each compared against 0)."""
    served, vocab = run.served, run.dims.vocab
    agents = run.window_agents()
    reqs = [served.reqs[rid] for a in agents for rid in a.rids]
    streamed = sum(len(r.tokens) for r in served.reqs.values())
    engine_tokens = (served.counters_end["tokens"]
                     - served.counters_open["tokens"])
    want = {a.index: len(run.traffic.agents[a.index].requests)
            for a in agents}
    return {
        "unfinished_agents": sum(a.done is None for a in agents),
        "missing_requests": sum(
            max(0, want[a.index] - len(a.rids)) for a in agents
            if a.done is not None
        ),
        "wrong_token_counts": sum(
            len(r.tokens) != run.request_spec(r)[1] for r in reqs
        ),
        "tokens_out_of_vocab": sum(
            int(np.sum((np.asarray(r.tokens) < 0)
                       | (np.asarray(r.tokens) >= vocab)))
            for r in reqs
        ),
        "first_tokens_unseen": sum(r.first_token is None for r in reqs),
        "engine_token_count_gap": abs(engine_tokens - streamed),
    }


def sample(run, n: int, seed: int) -> list:
    """n finished requests of the window's agents, drawn from the seed; the
    one with the most served tokens always among them."""
    done = [r for a in run.window_agents() if a.done is not None
            for r in (run.served.reqs[rid] for rid in sorted(a.rids))
            if r.first_token is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(run, reqs: list, length: int):
    """Per request: the input sequence (prompt, then every served token but
    the last), its served tokens as targets at positions p-1 .. p+d-1, and
    the number of valid positions; padded to ``length``."""
    out = []
    for r in reqs:
        prompt = np.asarray(run.request_spec(r)[0], np.int32)
        served = np.asarray([r.first_token] + r.tokens, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        p, n = len(prompt), len(seq)
        if n > length:
            raise ValueError(f"request of {n} tokens exceeds {length}")
        tokens = np.zeros(length, np.int32)
        tokens[:n] = seq
        targets = np.zeros(length, np.int32)
        targets[p - 1:n] = served
        valid = np.zeros(length, bool)
        valid[p - 1:n] = True
        out.append((tokens, targets, valid))
    return out


def widest(gaps: list):
    """The widest gap over every checked token; None if none was checked."""
    sizes = [float(g.max()) for g in gaps if g.size]
    return max(sizes) if sizes else None


def model_step(family, dims, params, seqs, control: bool = False) -> dict:
    """The readings of the model step: ``logit_gap``, the widest gap of a
    served token under the float32 reference, and the number of tokens
    checked; with ``control`` also the control's widest gap and the share
    of positions where the float8 pass puts another token first."""
    gaps, ctrl = [], []
    for tokens, targets, valid in seqs:
        gap, _ = family.token_gaps(dims, "f32", params, tokens, targets)
        gaps.append(np.asarray(gap)[valid])
        if control:
            _, low_first = family.token_gaps(dims, "fp8", params, tokens,
                                             np.zeros_like(tokens))
            gap, _ = family.token_gaps(dims, "f32", params, tokens,
                                       low_first)
            ctrl.append(np.asarray(gap)[valid])
    out = {"logit_gap": widest(gaps),
           "checked_tokens": int(sum(g.size for g in gaps))}
    if control:
        out["control_gap"] = widest(ctrl)
        out["control_mismatch_share"] = (
            float(sum((g > 0).sum() for g in ctrl)
                  / max(1, sum(g.size for g in ctrl))))
    return out
