"""Custom TPU (Pallas) kernels for the hot serving ops.

XLA's automatic fusion covers almost everything in this framework; kernels
live here only where a hand schedule measurably beats it: `attention`
(`latent_decode_attention`, decode over a latent cache;
`quant_decode_attention`, decode over folded int8 planes that reads each
lane's live positions alone), `ssm`
(`ssm_step`), `kda` (`kda_step`), `lightning` (`lightning_step`),
`sparse` (`sparse_append`, `sparse_select`, `sparse_decode`: a decode step
that reads the blocks it chose) and `shortconv` (`shortconv_step`: a kernel
for its name in a trace and its update in place, not for speed), each
imported by the model that calls it.
"""
