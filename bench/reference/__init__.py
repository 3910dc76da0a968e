"""Plain references of the codecs' stream formats.

Written from the formats' descriptions in plain ``jax.numpy``; nothing here
imports the program under test.  Each encoder takes a ``dtype``: float32 is
the configuration's precision, bfloat16 is the control that must fail the
comparison.
"""
