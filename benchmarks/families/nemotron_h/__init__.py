"""The nemotron_h family (NVIDIA Nemotron-3-Nano: Mamba-2 blocks, relu^2
routed experts of which a chip holds a share, one attention block without a
position signal): weights, reference, compare, roofline. `README.md` has the
equations and what came with the family."""
