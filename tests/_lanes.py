"""Per-lane views of recorded pool streams, for the tests."""

import numpy as np


def by_lane(ids, *streams):
    """Each step's rows in env-id order: the per-lane streams, which are
    what a pool promises; the order a block's rows came in is not."""
    o = np.argsort(ids, axis=1, kind="stable")
    return [np.take_along_axis(x, o.reshape(o.shape + (1,) * (x.ndim - 2)), 1)
            for x in (ids, *streams)]
