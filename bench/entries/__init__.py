"""Entry kinds: one module per way a cell drives the program."""
