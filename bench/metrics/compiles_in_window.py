"""Backend compilations while the measured window ran (should be 0).

Source: JAX's ``/jax/core/compile/backend_compile_duration`` monitoring
event, counted by the harness from the window's start to its end."""


def read(obs):
    return obs.get("compiles_in_window")
