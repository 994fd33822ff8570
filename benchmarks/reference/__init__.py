"""Plain float32 references, independent of the code under test."""
