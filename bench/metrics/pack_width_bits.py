"""Mean main-stream width bucket (bits) of the fields compressed in the
traced window: the capacity pass 2 packs at, so the work behind
``pack_ms``.

Read from the program's own counters ``<compressor>.compress.bucket_<w>``
(fields compressed at bucket ``w``), which the classic compress path
records in the ``repro.obs`` registry at its width read.  The harness
enables the registry for the traced window only, so the registry holds
the window's calls; a ``counters`` dict on the context takes precedence.
None when no bucket counter is there (the resident path records none).
"""


def mean_bucket(counters: dict, compressor: str):
    prefix = f"{compressor}.compress.bucket_"
    fields = {int(k[len(prefix):]): v for k, v in counters.items()
              if k.startswith(prefix)}
    total = sum(fields.values())
    if total <= 0:
        return None
    return sum(w * n for w, n in fields.items()) / total


def read(ctx):
    counters = getattr(ctx, "counters", None)
    if counters is None:
        from repro import obs
        counters = obs.snapshot()["counters"]
    return mean_bucket(counters, ctx.compressor)
