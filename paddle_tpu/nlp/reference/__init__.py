"""Plain float32 `jax.numpy` references of the model families under
`nlp/transformers`: no cache, no kernels, no batching. Tests and the
benchmark (which keeps its own copy) hold the served path to them:
`latent_moe` (latent attention + held experts) and `hybrid_linear`
(gated-delta-rule linear attention between full-attention layers)."""
