"""The chip benchmark's own code: discovery, traffic, weights, the plain
reference, the open/closed-loop driver, the trace reduction and the
roofline yardstick.  Nothing here is imported by the program under test."""
