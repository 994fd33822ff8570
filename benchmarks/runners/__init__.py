"""Runners: one module each, found by the ``runner`` a traffic file names."""
