"""The on-chip benchmark of this repository. Start at ``README.md``."""
