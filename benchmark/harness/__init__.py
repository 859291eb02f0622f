"""The general half of the yardstick: traffic generation, the reduction from
trace to metrics, peaks, a kernel's operations and bytes from its shapes, and
the comparison that decides ``correct``. Nothing here imports the program or
knows a model. A model's code (the seed's weights, the plain reference, the
counts, the program's builder) is a family under ``../families/``; only a
family's ``program.py`` and the runners under ``../runners/`` touch the
program, through its entry points."""
