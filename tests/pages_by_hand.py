"""Where a prompt's keys and values lie in a page pool, by a plain numpy loop:
the reference the pool writers of ``models/gpt.py`` are held to, so that no
writer is compared with itself."""

import numpy as np


def pages_by_hand(pool, dense, tables, lengths, starts):
    """``pool[l, h, tables[f, pos // ps], pos % ps] = dense[l, f, h, pos]``
    for ``starts[f] <= pos < lengths[f]``. ``pool`` [L, H, P, ps, Dh],
    ``dense`` [L, F, H, S, Dh]. Returns (the pool written, the mask of what
    was written)."""
    want = np.array(pool)
    dense = np.asarray(dense)
    written = np.zeros(want.shape, bool)
    ps = want.shape[3]
    for f, (start, length) in enumerate(zip(starts, lengths)):
        for pos in range(int(start), int(length)):
            page = int(tables[f][pos // ps])
            want[:, :, page, pos % ps] = dense[:, f, :, pos]
            written[:, :, page, pos % ps] = True
    return want, written
