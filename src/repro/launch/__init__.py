"""Launchers: the persistent compile cache (`compile_cache`) and the
hybrid serving CLI (`serve`)."""
