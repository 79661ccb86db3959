"""Host seconds of the program's ``layout.pack`` span, its self time: the
per-subject loop of ``bucketize`` that packs the host arrays of each bucket,
read from ``repro.core.spans`` in this process (set-up built the layout
here). Moves ``setup_s``."""


def read(ctx):
    try:
        from repro.core.spans import span_totals
    except ImportError:     # a program without host spans
        return None
    pack = span_totals().get("layout.pack")
    return pack["self_s"] if pack else None
