"""Operations and bytes of one answered step, and the table of peaks
(``bench/peaks.json``) keyed by JAX's ``device_kind``.

A step is one token through the whole model. Its cost is the work that the
input routes to, whatever the program implements: a program that reads
weights its input does not need gets no credit for them, and one that skips
weights its input does need cannot read above its roofline. The step cost
of a model is the mean of that work over one cycle of the program's inputs
(``harness.INPUTS``).

``read_once`` is the rule where every input reads every weight, the
default of a family without a ``step_cost`` of its own: FLOPs are 2 x the
elements of every weight matrix the step multiplies by, the output head
included, the embedding lookup excluded (a tied embedding is multiplied by
once, as the head); bytes are every weight it reads, a tied embedding once,
whole, as the head, an untied one as the single row that is looked up. A
family whose step reads weights chosen by the input, such as routed
experts, gives ``step_cost(m, params, token)``, computed from the seeded
weights (``bench/families/__init__.py``). Activations are left out, so
both are lower bounds of what the step must do.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Sequence, Tuple

from bench import BenchError, families, weights

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def read_once(tree) -> Tuple[int, int]:
    """(FLOPs, bytes) of a step that reads every leaf of the layout ``tree``
    once, and the embedding as one row unless it is tied."""
    embed = tree["head"]["embed"]
    tied = "lm_head" not in tree["head"]
    flops, nbytes = 0, 0
    for _, leaf in weights.leaves_with_paths(tree):
        if leaf is embed:
            vocab, d = leaf.shape
            if tied:
                flops += 2 * vocab * d
                nbytes += leaf.nbytes
            else:
                nbytes += leaf.nbytes // vocab
            continue
        nbytes += leaf.nbytes
        if leaf.kind == "dense":
            flops += 2 * math.prod(leaf.shape)
    return flops, nbytes


def step_cost(m: dict, params=None, tokens: Sequence[int] = ()) -> Tuple[float, float]:
    """Mean (FLOPs, bytes) of one answered one-token step of model entry
    ``m`` over the inputs ``tokens``. A family whose cost depends on the
    input needs the seeded weights ``params`` and the inputs."""
    family = families.load(m["model_type"])
    per_input = getattr(family, "step_cost", None)
    if per_input is None:
        return read_once(family.layout(m))
    if params is None or not tokens:
        raise BenchError(f"{m['model_type']}: a step's cost depends on its input: give the weights and the inputs")
    each = [per_input(m, params, tok) for tok in tokens]
    return sum(f for f, _ in each) / len(each), sum(b for _, b in each) / len(each)


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a device missing from the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
