"""In-place writes of new K/V rows into a stacked dense KV cache.

A decode step adds one ``(Hkv, Dh)`` row per request to each attention
layer's cache; rewriting the whole ``(B, Hkv, S, Dh)`` cache to do so
moves ``S`` times the bytes the step needs.  ``kernel.py`` writes only
the 128-position block that holds each request's row, with the cache
aliased to the output; ``ops.py`` dispatches it (the kernel on a TPU, an
XLA scatter elsewhere) and declares its VMEM footprint.
"""
