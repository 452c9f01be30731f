"""Custom TPU (Pallas) kernels for the hot serving ops.

XLA's automatic fusion covers almost everything in this framework; kernels
live here only where a hand schedule measurably beats it: `attention`
(`latent_decode_attention`, decode over a latent cache), `ssm`
(`ssm_step`) and `kda` (`kda_step`), each imported by the model that
calls it.
"""
