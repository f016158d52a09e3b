"""Digests of the weights, which show that the program's copies are intact.

The harness digests every weight it makes from the seed, on the device and
on the host, before the program holds it. After the window it digests the
program's host copy of every segment and the device copy of every resident
one. A copy whose digest differs was altered on its way to the host or back.
The logits of one-token decodes cannot show all of that: with one key the
attention's query and key weights never reach them, and a state of zeros
hides Mamba2's decay and most of its convolution taps.

Host digests are CRC-32s of 64 MiB chunks, taken on several threads; device
digests are two position-weighted sums of the raw bits, modulo 2**32, so a
chunk zeroed, left stale or moved changes them.
"""
from __future__ import annotations

import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CHUNK = 64 << 20
THREADS = 8
_UINT = {1: "uint8", 2: "uint16", 4: "uint32"}


def host_digests(arrays: Sequence[np.ndarray]) -> List[Tuple[int, ...]]:
    """For each array, its shape, dtype and the CRC-32 of each chunk."""
    flat = [np.ascontiguousarray(a).reshape(-1).view(np.uint8) for a in arrays]
    jobs = [(i, lo) for i, b in enumerate(flat) for lo in range(0, max(b.size, 1), CHUNK)]
    with ThreadPoolExecutor(THREADS) as pool:
        crcs = list(pool.map(lambda job: zlib.crc32(flat[job[0]][job[1]:job[1] + CHUNK]), jobs))
    out: Dict[int, list] = {i: [a.shape, str(a.dtype)] for i, a in enumerate(arrays)}
    for (i, _), crc in zip(jobs, crcs):
        out[i].append(crc)
    return [tuple(out[i]) for i in range(len(arrays))]


@jax.jit
def _device_digest(x):
    w = lax.bitcast_convert_type(x, jnp.dtype(_UINT[x.dtype.itemsize])).astype(jnp.uint32).reshape(-1)
    i = lax.iota(jnp.uint32, w.size)
    a = jnp.sum(w * (i * np.uint32(0x9E3779B1) + np.uint32(1)), dtype=jnp.uint32)
    b = jnp.sum((w ^ (i * np.uint32(0x85EBCA77))) * np.uint32(0xC2B2AE3D), dtype=jnp.uint32)
    return jnp.stack([a, b])


def device_digests(arrays) -> List[Tuple[int, ...]]:
    """For each device array, its shape, dtype and two sums of its bits."""
    sums = [_device_digest(x) for x in arrays]
    return [(tuple(x.shape), str(x.dtype), *map(int, np.asarray(s))) for x, s in zip(arrays, sums)]


def mismatched(server, expected: Dict[int, dict]) -> List[str]:
    """The copies of the program's segments whose digests differ from those
    of the weights the harness made: ``<model>/<path> host`` or ``device``.
    ``expected[model]`` holds ``host`` and ``device`` digests in segment
    order."""
    bad = []
    for i, task in server.runtime.tasks.items():
        segs = task.segments
        for seg, got, want in zip(segs, host_digests([s.host for s in segs]), expected[i]["host"]):
            if got != want:
                bad.append(f"{task.cfg.name}/{seg.path} host")
        resident = [(k, s) for k, s in enumerate(segs) if s.device is not None]
        got = device_digests([s.device for _, s in resident])
        for (k, seg), g in zip(resident, got):
            if g != expected[i]["device"][k]:
                bad.append(f"{task.cfg.name}/{seg.path} device")
    return bad
