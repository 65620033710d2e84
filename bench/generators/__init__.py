"""The benchmark's own data generators: graphs and query streams.

Kept apart from the program's generators so that a change to the program
cannot move the yardstick. A configuration file names its graph generator
by ``generator``; a traffic file is read by :func:`queries.batch`.
"""
