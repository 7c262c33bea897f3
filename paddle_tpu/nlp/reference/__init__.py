"""Plain float32 `jax.numpy` references of the model families under
`nlp/transformers`: no cache, no kernels, no batching. Tests and the
benchmark (which keeps its own copy) hold the served path to them."""
