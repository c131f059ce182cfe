"""The benchmark's own yardstick: traffic generation, clients, arithmetic,
trace reduction, roofline functions, peaks and the plain reference.  Nothing
here is imported by the program under test, and the parent process
(bench/run.py) never imports JAX."""
