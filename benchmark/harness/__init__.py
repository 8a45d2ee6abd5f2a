"""The benchmark's own code: traffic, load generator, metric arithmetic,
trace reduction, plain reference, peaks.  Nothing here is imported by the
package, and only ``server.py`` imports the package."""
