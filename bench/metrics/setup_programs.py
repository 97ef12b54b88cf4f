"""Programs JAX compiled, or loaded from its persistent cache, in set-up:
one per decode batch size, suffix length and the like that the traffic
makes.  The program compiles its steps per shape; this count, and the
set-up time it costs, fall when it buckets shapes."""


def read(run):
    return run["setup_programs"] or None
