"""Traffic generators, one module per mix kind, and the mixes they read."""
