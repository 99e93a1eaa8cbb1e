"""repro: reproduction of "On Optimally Partitioning Variable-Byte Codes"
grown into a jax/pallas serving system."""
