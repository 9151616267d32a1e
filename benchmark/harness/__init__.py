"""The benchmark's harness: everything that is general to all cells."""
