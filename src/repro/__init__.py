"""repro — out-of-core edge partitioning (2PS-L) + the SPMD runtime it feeds.

Importing the package touches no JAX state: the platform comes from JAX's
own configuration (``JAX_PLATFORMS``), and the persistent compile cache is
placed by the launchers (``repro.compile_cache``), never at import.
"""
