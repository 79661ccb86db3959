"""Paper core: PARAFAC2 + SPARTan MTTKRP on bucketed compressed-column data."""
from repro.core.irregular import (
    Bucket, Bucketed, ScatterIndex, SparseBucket, bucketize, bucket_format,
    cc_bucket_like, FORMATS)
from repro.core.backend import MttkrpBackend, get_backend
from repro.core.compress import (
    CompressedBucket,
    CompressedData,
    Preprocess,
    available as available_preprocess,
    parse_preprocess_spec,
    preprocess_summary,
    register_preprocess,
)
from repro.core.constraints import (
    Constraint,
    available as available_constraints,
    parse_constraint_arg,
    parse_spec as parse_constraint_spec,
)
from repro.core.parafac2 import (
    Parafac2Options,
    Parafac2State,
    als_step,
    constraints_for,
    fit,
    init_state,
    reconstruct_uk,
    update_subjects,
    w_global,
)
from repro.core.engine import (
    ENGINES, fit_device, make_als_chunk, make_als_while, make_subject_update)

__all__ = [
    "CompressedBucket",
    "CompressedData",
    "Preprocess",
    "available_preprocess",
    "parse_preprocess_spec",
    "preprocess_summary",
    "register_preprocess",
    "cc_bucket_like",
    "Constraint",
    "available_constraints",
    "constraints_for",
    "parse_constraint_arg",
    "parse_constraint_spec",
    "ENGINES",
    "fit_device",
    "make_als_chunk",
    "make_als_while",
    "Bucket",
    "Bucketed",
    "ScatterIndex",
    "SparseBucket",
    "bucketize",
    "bucket_format",
    "FORMATS",
    "MttkrpBackend",
    "get_backend",
    "Parafac2Options",
    "Parafac2State",
    "als_step",
    "fit",
    "init_state",
    "make_subject_update",
    "reconstruct_uk",
    "update_subjects",
    "w_global",
]
