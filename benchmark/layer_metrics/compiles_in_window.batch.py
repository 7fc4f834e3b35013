"""Programs compiled, or loaded from the persistent cache, inside the window
(jax.monitoring events counted by the harness). Should read 0: every shape is
warmed up in set-up."""


def read(run: dict):
    return run["compiles_in_window"] if run.get("jobs") else None
