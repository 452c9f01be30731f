"""The minicpm_sala family (OpenBMB MiniCPM-SALA: InfLLM-v2 block-sparse
attention layers that choose the blocks they read through pooled keys, beside
Lightning linear-attention layers with a matrix state, under a muP-scaled
trunk): weights, reference, compare, roofline. `README.md` has the equations
and what came with the family."""
