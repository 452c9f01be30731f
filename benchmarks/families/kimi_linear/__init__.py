"""The kimi_linear family (Moonshot Kimi-Linear-48B-A3B: Kimi Delta Attention
layers with a matrix state, latent attention without a position signal,
SwiGLU routed experts of which a chip holds a share): weights, reference,
compare, roofline. `README.md` has the equations and what came with the
family."""
