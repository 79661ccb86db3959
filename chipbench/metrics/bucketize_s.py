"""Host seconds in ``repro.launch.decompose.build_buckets`` (the bucket plan,
format routing, ``bucketize`` and the transfer to the device), from the
benchmark's own span around the call. Moves ``setup_s``."""


def read(ctx):
    return ctx.spans.get("bucketize_s")
