"""The yardstick: traffic generation, weights from the seed, the plain
reference, the reduction from trace to metrics, peaks, flops from shapes and
the comparison that decides ``correct``. Nothing here imports the program;
only the runners (serve.py, train.py) touch it, through its entry points."""
