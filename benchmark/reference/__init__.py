"""Plain references: the same SQL semantics in numpy on the same Arrow
tables. Nothing here imports the program or takes anything it has made."""
