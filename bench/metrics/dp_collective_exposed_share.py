"""Share of the data-parallel step in which the gradient all-reduce runs
with no other operation on the same device, in %: exposed all-reduce
time over the traced window, averaged over the chips."""

#: XLA's collective ops (``dp/reduce_gradients`` with ``psum`` is an
#: all-reduce; an async one shows as its start and its wait for done)
COLLECTIVES = r"^(all-reduce|all-gather|reduce-scatter|collective-permute)"


def read(run):
    t = run.reduced_trace
    if t is None or t.op_count(COLLECTIVES) == 0:
        return None
    return 100.0 * t.exposed_seconds(COLLECTIVES) / t.window_s
