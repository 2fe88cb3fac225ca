"""Programs jax built inside the measured window, compiled or loaded from
the cache, counted by the benchmark's listener on jax's compile event.
Must be 0, or `correct` is false. Layer: Compile_cache. Moves
train_samples_per_s."""


def compute(run: dict, trace: dict | None):
    return run["compiles_in_window"]
