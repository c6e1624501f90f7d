"""Plain references of the models: the equations in straightforward
``jax.numpy``, float32, no kernels, no blocks -- what tests compare the
program's models with.  They import nothing of the program."""
