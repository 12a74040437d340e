"""Plain float32 references the benchmark decides ``correct`` against.

Nothing here imports the program (``horovod_tpu``), flax or optax, and
nothing takes a value the program made: weights, batches and optimizer
state all come from the benchmark's seed.
"""
