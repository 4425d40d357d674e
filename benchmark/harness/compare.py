"""The comparison that decides `correct`: what the timed path served in the
window against the plain reference of the configuration's architecture
(reference/<architecture>.py, through harness/seam.py).

After the window has closed, the peak has been read and the program's state
is freed, a sample of the window's waves goes through the reference once:
the prompt as the program tokenised it, followed by the tokens it served.
Waves submitted against the same prompt prefix (one snapshot of the cluster)
form a group and share one forward pass; the sample is whole groups, the one
holding the longest request first, the others drawn from the seed. Compared:

- worst_gap: over every served token that was a choice (the grammar allowed
  more than one), how far its float32 reference logit lies under the best
  allowed token's. Greedy decode serves the best token, so only rounding
  separates the two;
- mean_gap: the same gap, averaged over every choice sampled. A served token
  lies under the best only where rounding outweighs the margin between the
  two, which happens in proportion to the rounding and leaves a gap in
  proportion to it: the mean grows with the square of the arithmetic's error
  where the widest gap grows with the error itself, so it holds a lower
  precision apart from the stated one by far more, and does not hang on the
  one choice with the thinnest margin;
- grammar_violations: served tokens the grammar (written out again in the
  reference) did not allow at their place;
- prompt_mismatches: sampled prompts whose text is not what the generator
  sent (pod name and requests in the suffix; every node of the cluster, once
  each and in order, in the prefix's pinned part and its VALID NODE NAMES);
- bind_mismatches: sampled pods bound to another node than the served
  decision names;
- unfinished: sampled decisions that do not end in end-of-sequence.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import seam  # noqa: E402
from harness.traffic import rng_for  # noqa: E402
from reference.grammar import Grammar  # noqa: E402

SAMPLE_WAVES = 12  # about 2,000 choices: enough that int8 moves dozens of them
GROUP_WAVES = 6    # waves of one group that share a forward pass


def limits_for(conf: dict) -> dict:
    """limits/<configuration>.json: a file to a configuration, so that a
    later PR adds one with its configuration."""
    table = json.loads((BENCH / "limits" / f"{conf['name']}.json").read_text())
    return {k: v for k, v in table.items() if k != "comment"}


def reference_tokenizer():
    """The committed BPE fixture, read with the tokenizers library itself."""
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(
        str(REPO / "k8s_llm_scheduler_tpu" / "assets" / "bpe4k"), local_files_only=True)
    return tok


def sample_groups(waves: list[dict], seed: int, k: int = SAMPLE_WAVES,
                  per_group: int = GROUP_WAVES) -> list[list[dict]]:
    """About k of the window's waves as whole groups (waves that share a
    prefix, at most per_group of each): the group holding the longest
    request, then groups in an order drawn from the seed."""
    if not waves:
        return []
    groups: dict[tuple, list[dict]] = {}
    for w in waves:
        groups.setdefault(tuple(w["prefix_ids"]), []).append(w)

    def longest(w):
        return max(len(p) + len(s) for p, s in zip(w["prompts"], w["served"])) + len(w["prefix_ids"])

    keys = list(groups)
    first = max(keys, key=lambda key: max(longest(w) for w in groups[key]))
    rest = [key for key in keys if key != first]
    rng = rng_for(seed, "sample")
    rng.shuffle(rest)
    out, taken = [], 0
    for key in [first] + rest:
        if taken >= k:
            break
        members = sorted(groups[key], key=longest, reverse=True) if key == first else list(groups[key])
        if key != first:
            rng.shuffle(members)
        members = members[: min(per_group, k - taken)]
        out.append(members)
        taken += len(members)
    return out


def gaps_for_group(conf, weights, group, grammar, vocab_rows, control: bool = False):
    """(gaps, control gaps, violations, unfinished) for the waves of one
    group: every row of every wave is a tail behind the shared prefix. With
    `control`, a second list judges at each choice not the served token but
    the one the int8 forward puts first among the allowed."""
    ref = seam.reference(conf)
    tails, spans, allowed_all, served_all = [], [], [], []
    for wave in group:
        for suffix, served in zip(wave["prompts"], wave["served"]):
            tails.append(list(suffix) + list(served))
            spans.append((len(suffix) - 1, len(served)))
            allowed_all.append(grammar.walk(list(served)))
            served_all.append(served)
    prefix = group[0]["prefix_ids"]
    logits = ref.wave_logits(conf, weights, prefix, tails, spans, "f32", vocab_rows)
    low = ref.wave_logits(conf, weights, prefix, tails, spans, "int8", vocab_rows) if control else None
    gaps, low_gaps, violations, unfinished, row = [], [], 0, 0, 0
    for served, allowed in zip(served_all, allowed_all):
        if not served or served[-1] != grammar.tail[-1]:
            unfinished += 1
        for tok, ok in zip(served, allowed):
            if tok not in ok:
                violations += 1
            elif len(ok) > 1:
                best = float(np.max(logits[row, ok]))
                gaps.append(best - float(logits[row, tok]))
                if control:
                    low_gaps.append(best - float(logits[row, ok[int(np.argmax(low[row, ok]))]]))
            row += 1
    return gaps, low_gaps, violations, unfinished


_NAME = re.compile(r"Name: default/(\S+)")
_CPU = re.compile(r"CPU request: ([0-9.]+) cores")
_MEM = re.compile(r"Memory request: ([0-9.]+) GB")
_NODE = re.compile(r"\"selected_node\": \"([^\"]+)\"")


def text_checks(tok, wave, plans: dict, node_names: list[str], bindings: dict) -> tuple[int, int]:
    """(prompt_mismatches, bind_mismatches) of one wave."""
    prompt_bad = bind_bad = 0
    prefix = tok.decode(list(wave["prefix_ids"]))
    head = prefix.split("STATE UPDATES", 1)[0]
    if re.findall(r"Node: (\S+)", head) != node_names:
        prompt_bad += len(wave["prompts"])
    valid = re.search(r"VALID NODE NAMES: \[([^\]]*)\]", head)
    if valid is None or valid.group(1).split(", ") != node_names:
        prompt_bad += len(wave["prompts"])
    for suffix, served in zip(wave["prompts"], wave["served"]):
        text = tok.decode(list(suffix))
        name, cpu, mem = _NAME.search(text), _CPU.search(text), _MEM.search(text)
        plan = plans.get(name.group(1)) if name else None
        if (plan is None or cpu is None or mem is None
                or cpu.group(1) != f"{plan.cpu_m / 1000:.3f}"
                or mem.group(1) != f"{plan.mem_mi / 1024:.3f}"):
            prompt_bad += 1
            continue
        node = _NODE.search(tok.decode(list(served)))
        if node is None or bindings.get(plan.name) != node.group(1):
            bind_bad += 1
    return prompt_bad, bind_bad


def _stats(gaps: list[float]) -> dict:
    """worst, mean and count of the gaps over zero (a choice served, or
    put first, under the reference's best)."""
    if not gaps:
        return {"worst_gap": float("inf"), "mean_gap": float("inf"), "moved": 0}
    return {"worst_gap": max(gaps), "mean_gap": sum(gaps) / len(gaps),
            "moved": sum(1 for g in gaps if g > 0)}


def compare(conf: dict, seed: int, waves: list[dict], plans: dict, node_names: list[str],
            bindings: dict, max_reason: int, control: bool = False, weights=None) -> dict:
    """The numbers compared, each beside its limit, and `correct`. With
    `control` the int8 control's gaps are judged in the program's place
    (tests/read_limits.py, tests/test_faults.py); the program's own are
    returned beside them."""
    limits = limits_for(conf)
    tok = reference_tokenizer()
    encode = lambda s: tok.encode(s, add_special_tokens=False)  # noqa: E731
    grammar = Grammar(encode, tok.eos_token_id, sorted(node_names), max_reason)
    sample = sample_groups(waves, seed)
    if weights is None:
        weights = seam.reference(conf).init_weights(conf, conf["weights_seed"])
    gaps, low_gaps, violations, unfinished, prompt_bad, bind_bad, tokens = [], [], 0, 0, 0, 0, 0
    for group in sample:
        g, lg, v, u = gaps_for_group(conf, weights, group, grammar, len(tok), control)
        gaps.extend(g)
        low_gaps.extend(lg)
        violations += v
        unfinished += u
        for wave in group:
            tokens += sum(len(s) for s in wave["served"])
            p, b = text_checks(tok, wave, plans, node_names, bindings)
            prompt_bad += p
            bind_bad += b
    program = _stats(gaps)
    low = _stats(low_gaps) if control else None
    judged = low if control else program
    numbers = {
        "worst_gap": judged["worst_gap"], "mean_gap": judged["mean_gap"],
        "grammar_violations": violations, "prompt_mismatches": prompt_bad,
        "bind_mismatches": bind_bad, "unfinished": unfinished,
    }
    compared = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    n_waves = sum(len(g) for g in sample)
    return {
        "correct": bool(sample) and all(c["value"] <= c["limit"] for c in compared.values()),
        "compared": compared,
        "program": program,
        "control": low,
        "gap_lists": {"program": gaps, "control": low_gaps},
        "sampled": {"groups": len(sample), "waves": n_waves,
                    "requests": sum(len(w["served"]) for g in sample for w in g),
                    "served_tokens": tokens, "choices": len(gaps)},
    }
