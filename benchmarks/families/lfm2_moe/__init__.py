"""The lfm2_moe family (LFM2-8B-A1B): found by a configuration file's
`"family": "lfm2_moe"` (gated short-convolution layers 3 : 1 with
grouped-query attention, 32 routed experts top-4 held whole): weights,
reference, compare, roofline. `README.md` has the equations and what came
with the family.
"""
