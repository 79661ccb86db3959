"""Pallas TPU kernels and jnp contractions for the SPARTan ALS hot spots.

``fused``   the four fused slab-pass Pallas kernels of the CC fused route
``scoo``    the O(nnz) jnp gathers and segment sums of the SCOO route
``segsum``  the mode-2 segment sum (a Pallas segmented prefix sum on a TPU)
``sketch``  the randomized range-finder sketch of the compression stage
"""
