"""Plain numpy references, independent of the code under test."""
