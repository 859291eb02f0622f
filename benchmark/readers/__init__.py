"""One small reader per per-layer metric (or per family of them): each takes
the run's context and returns the metric's value, or None where it finds
nothing to read — never 0 for a share of a roofline or of a peak."""
