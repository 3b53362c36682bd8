"""Plain references, one module per algorithm, found by the name a
configuration file gives under ``"reference"``."""
