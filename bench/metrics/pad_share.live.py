"""Padding in the server's waves: 1 - valid samples / (slots x bucket
length summed over the steps run in the window), from the server's
``bucket_counts``, in %."""


def read(run):
    padded = sum(run.capacity * int(L) * n for L, n in run.buckets.items())
    if not padded:
        return None
    return 100.0 * (1.0 - run.result["samples"] / padded)
