"""Plain references, independent of the program under test."""
