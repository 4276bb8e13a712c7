"""Traffic generators, one module per kind; each reads a mix's data file."""
