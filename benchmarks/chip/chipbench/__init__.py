"""The chip benchmark's harness: traffic, set-up and window, reference
check, trace reduction, and the arithmetic of operations, bytes and
peaks. ``run.py`` beside this package is the entry point."""
