"""SK Telecom's axk1 (A.X-K1): latent attention (MLA) over a cache with no
heads, a leading dense layer, routed experts of which one chip holds a
share, a shared expert. README.md beside this file has the layer equations,
what the family reads and where its limits come from."""
