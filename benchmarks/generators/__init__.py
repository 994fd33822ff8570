"""Traffic generators: one module each, found by the ``generator`` a traffic file names."""
