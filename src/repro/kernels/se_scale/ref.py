"""Pure-jnp oracle for the squeeze-and-excitation gate kernel."""
import jax
import jax.numpy as jnp


def se_scale_ref(x: jax.Array, g: jax.Array) -> jax.Array:
    """x: [N, H, W, C], g: [N, C] -> x * g per frame and channel."""
    y = x.astype(jnp.float32) * g.astype(jnp.float32)[:, None, None, :]
    return y.astype(x.dtype)
