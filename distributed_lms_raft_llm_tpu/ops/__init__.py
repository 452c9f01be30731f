"""Custom TPU (Pallas) kernels for the hot serving ops.

XLA's automatic fusion covers almost everything in this framework; kernels
live here only where a hand schedule measurably beats it: `attention`
(`latent_decode_attention`, decode over a latent cache), `ssm`
(`ssm_step`), `kda` (`kda_step`), `lightning` (`lightning_step`) and
`sparse` (`sparse_append`, `sparse_select`, `sparse_decode`: a decode step
that reads the blocks it chose), each imported by the model that calls it.
"""
