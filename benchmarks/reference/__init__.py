"""Plain float32 ``jax.numpy`` references. Nothing here imports the program
(``perceiver_io_tpu``) or takes anything the program made."""
