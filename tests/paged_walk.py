"""Block tables and lengths at the edges of the paged decode kernel's
walk, shared by the kernel's parity tests."""
import numpy as np


def paged_walk_case(kv, d, bs, itemsize, seed=0):
    """Block tables and lengths at the edges of the paged decode kernel's
    walk, which copies ``p = pages_per_step(...)`` pages a step: lengths
    0, ``p*bs - 1``, ``p*bs`` and ``nb*bs - 1``, over tables ``nb`` wide,
    two groups and a part (not a multiple of ``p``). Each slot owns its
    own blocks up to its last live page; the rest of its table points at
    trash block 0. Returns ``(tables, lengths, nblocks, dead, p)``, where
    ``dead`` (nblocks, bs) marks the pool positions no slot attends: the
    trash block, and each slot's last page past its length."""
    from repro.kernels.paged_attention import pages_per_step
    p = pages_per_step(bs, kv, d, itemsize, 1 << 20)
    nb = 2 * p + max(p // 2, 1)
    assert nb % p
    lengths = np.array([0, p * bs - 1, p * bs, nb * bs - 1], np.int32)
    live = lengths // bs + 1
    nblocks = int(live.sum()) + 1
    ids = np.random.default_rng(seed).permutation(np.arange(1, nblocks))
    tables = np.zeros((len(lengths), nb), np.int32)
    dead = np.ones((nblocks, bs), bool)
    at = 0
    for r, n in enumerate(live):
        tables[r, :n] = ids[at:at + n]
        at += n
        pos = np.arange(n * bs).reshape(n, bs)
        dead[tables[r, :n]] = pos > lengths[r]
    return tables, lengths, nblocks, dead, p
