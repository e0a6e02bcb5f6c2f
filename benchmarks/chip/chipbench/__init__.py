"""The chip benchmark's yardstick: data, reference, trace reduction, work.

Of the program under test, only ``harness`` imports anything (the
compile-cache switch), and the adapters under ``systems/`` drive it; the
reference, the comparison, the trace reduction and the work counts do
not touch it.
"""
