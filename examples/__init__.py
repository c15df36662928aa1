"""Example scripts (`JAX_PLATFORMS=cpu python examples/...` runs them
CPU-only; unset, JAX uses the attached accelerator)."""
