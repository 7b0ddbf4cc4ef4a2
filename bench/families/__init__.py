"""The harness side of each model family, one file per family
(``bench/harness/spec.py`` says what such a file defines)."""
