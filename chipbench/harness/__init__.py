"""The chip benchmark's harness: discovery, traffic, weights, the served
window, the trace reduction, the cost functions and the output check.

Nothing here is imported by the program under test; the harness imports
the program (``src/repro``) only to build the system it measures.
"""
