"""Plain references, one file per architecture family."""
