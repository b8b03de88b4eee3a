"""Clock-pair comparisons the stacked kernel launches of the cluster plane
run, over those of the tensors stacked: ``N*K*K*R`` at the shape bucket
(``repro.core.batched.bucket_shape``) of every kernel call in the window
(``kernel_shapes``, the calls' stacked shapes), over the program's counter
``plane.stack.comparisons`` (the same at each stacked tensor's own shape),
recorded while a profile is being taken, which in a traced run is the
window alone.  It counts both the stack's padding to its largest ``K`` and
the bucket's to a power of two.  A program without that counter gives
nothing."""


def read(w):
    try:
        from repro import trace
        from repro.core.batched import bucket_shape
    except ImportError:
        return None
    pairs = trace.snapshot()["counters"].get("plane.stack.comparisons", 0)
    if not pairs:
        return None
    launched = 0
    for shapes in w.get("kernel_shapes", {}).values():
        for shape, calls in shapes.items():
            n, k, r = bucket_shape(*shape)
            launched += calls * n * k * k * r
    return launched / pairs
