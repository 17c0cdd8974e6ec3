"""The benchmark's own tests run on the CPU (``JAX_PLATFORMS=cpu``)."""
import jax

jax.config.update("jax_enable_x64", False)
