"""The yardstick: traffic, sink, estimators, trace reduction, comparison.

Nothing here is imported by the program, and later PRs may not edit it.
"""
