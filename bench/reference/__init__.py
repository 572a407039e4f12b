"""The plain reference the served results are compared with.

Written from the configuration files and the published descriptions;
it imports nothing of the program and takes nothing the program made.
"""
