"""The benchmark's harness: everything that is not one configuration, one
traffic mix or one metric. See ``benchmark/README.md``."""
