"""Operations and bytes of one answered step, from the layout's shapes, and
the table of peaks (``bench/peaks.json``) keyed by JAX's ``device_kind``.

A step is one token through the whole model. Its FLOPs are 2 x the elements
of every weight matrix it multiplies by: the output head included, the
embedding lookup excluded (a tied embedding is multiplied by once, as the
head). Its bytes are every weight it reads: a tied embedding once, whole, as
the head; an untied one as the single row that is looked up. Activations are
left out, so both are lower bounds of what the step must do.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Tuple

from bench import weights

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def step_cost(m: dict) -> Tuple[int, int]:
    """(FLOPs, bytes) of one answered one-token step of model entry ``m``."""
    tree = weights.layout(m)
    embed = tree["head"]["embed"]
    tied = "lm_head" not in tree["head"]
    flops, nbytes = 0, 0
    for path, leaf in weights.leaves_with_paths(tree):
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


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a device missing from the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
