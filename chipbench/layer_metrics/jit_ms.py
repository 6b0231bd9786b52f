"""Operator build: host time JAX spends tracing, lowering and compiling, or
loading the compiled program from its cache, per window operation (the
operator is rebuilt by every task)."""


def read(run):
    if not run.jit or not run.ops:
        return None
    return 1e3 * sum(seconds for _, _, seconds in run.jit) / len(run.ops)
