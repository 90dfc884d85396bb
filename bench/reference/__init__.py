"""Plain references the cells' ``correct`` compares with; none imports the
system under test."""
