"""The unit sphere, genus 0: the reference's copy of the program's
``repro.core.gson.sampling.sample_sphere`` (radius 1), uniform by
normalised Gaussian draws; the genus-0 surface of arXiv:1503.08294's
reconstruction benchmarks ('bunny'-class)."""
import jax
import jax.numpy as jnp

GENUS = 0


def sample(rng, n):
    v = jax.random.normal(rng, (n, 3))
    v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
    return v.astype(jnp.float32)
