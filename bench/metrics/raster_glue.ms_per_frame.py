"""Device time of the rasterizer's XLA work around the Pallas kernels (the
``raster`` and ``loss`` work scopes: attribute gathers, the stash, the GMU
merge, the losses and PSNR) in the window, per frame, by
``bench/scopes.py``'s attribution of the trace; the kernels are left out."""

from bench import scopes


def read(run):
    d = scopes.split(run)
    return None if d is None else d["work"]["raster_glue"]
