"""The benchmark's own yardstick: launch, load, statistics, trace reduction,
peak table, float32 reference. Nothing here imports jax in the parent process;
the two modules that need it (reference.py, xplane.py) run as children."""
