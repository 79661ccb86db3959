"""Share of the layout's padded slots that hold no data, in %: over all
buckets, the padded-but-empty slots over all padded slots. A SCOO bucket's
slots are its Kb x N_pad triplets, a CC bucket's its Kb x I_pad x C_pad
cells; each record of ``ctx.buckets`` (``BucketPlan.stats`` as
``repro.launch.decompose.build_buckets`` returns it) gives the bucket's own
``pad_share`` in its routed format's currency. Returns None where a record
lacks it (a program whose plan does not report it). Moves ``iter_s``."""


def slots(rec):
    """Padded slots of one bucket record in its format's currency."""
    per_subject = (rec["nnz_pad"] if rec["format"] == "scoo"
                   else rec["i_pad"] * rec["c_pad"])
    return rec["n_subjects"] * per_subject


def read(ctx):
    recs = ctx.buckets or []
    if not recs or any("pad_share" not in r for r in recs):
        return None
    total = sum(slots(r) for r in recs)
    empty = sum(r["pad_share"] * slots(r) for r in recs)
    return 100.0 * empty / total if total else None
